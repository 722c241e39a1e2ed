"""Public entry points for the segmented primitives — backend dispatched.

The paper reduces every process-mining algorithm to a handful of columnar
dataframe operations (§5.3–5.4); these four primitives are that handful,
named, with two interchangeable lowerings each (see ``core.backend``):

=================  ====================================  ===================
primitive          paper operation (§5.3/5.4, Table 3)   lowerings
=================  ====================================  ===================
``segment_reduce`` group(D, case) + aggregate            xla scatter / pallas
``histogram``      counting ``c(e)`` after proj          xla scatter / pallas
``pair_count``     shift + mergstrv + count (DFG)        xla / matmul / pallas
``segmented_scan`` case-local fold (variants, EFG)       xla scan / pallas
=================  ====================================  ===================

Dispatch: an explicit ``impl=`` wins; otherwise ``core.backend.resolve()``.
One guardrail: float accumulation is order-sensitive, and the streaming
engine promises *bitwise* streaming == whole-log results.  The XLA scatter
accumulates in row order (chunking-invariant); the Pallas tilings do not.
Integer accumulation is exact under any order, so counting always takes the
fast path — but inexact-float weighted sums fall back to the XLA lowering
unless the caller passes ``assume_exact=True`` (asserting the values are
integer-valued, e.g. one-hot prefix counts) or forces an ``impl``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref as _ref
from .histogram import histogram_pallas
from .pair_count import pair_count_pallas
from .segment_reduce import segment_reduce_pallas
from .segmented_scan import (segmented_affine_pallas,
                             segmented_polyhash_pallas,
                             segmented_sum_scan_pallas)

reduce_identity = _ref.reduce_identity


def _backend():
    # deferred: core.backend's parent package imports core.dfg, which
    # imports this package — a module-level import here would re-enter
    # segment_ops mid-init and bind submodules in place of these functions
    from repro.core import backend

    return backend


def _inexact(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)


def _interpret() -> bool:
    return _backend().interpret_mode()


def _resolve(impl: str | None, order_sensitive: bool, assume_exact: bool) -> str:
    # an explicit impl is taken verbatim: pair_count also knows "matmul"
    if impl is not None:
        return impl
    return _backend().resolve(order_sensitive=order_sensitive,
                              assume_exact=assume_exact)


def segment_reduce(values: jax.Array, segment_ids: jax.Array,
                   num_segments: int, op: str = "sum", *,
                   impl: str | None = None, assume_exact: bool = False,
                   block_e: int = 512) -> jax.Array:
    """(num_segments,) ``op``-reduction of ``values`` grouped by sorted ids.

    ``segment_ids`` must be the sorted, consecutive ids produced by
    ``ops.segment_ids_sorted`` / ``engine.global_segments``; out-of-range
    ids (including -1) are dropped.  Empty segments hold the op identity.
    """
    was_bool = values.dtype == jnp.bool_
    vals = values.astype(jnp.int32) if was_bool else values
    chosen = _resolve(impl, op == "sum" and _inexact(vals), assume_exact)
    if chosen == "pallas":
        out = segment_reduce_pallas(vals, segment_ids, num_segments, op,
                                    block_e=block_e,
                                    interpret=_interpret())
    elif chosen == "xla":
        out = _ref.segment_reduce_ref(vals, segment_ids, num_segments, op)
    else:
        raise ValueError(f"unknown segment_reduce impl {chosen!r}")
    if was_bool and op in ("min", "max"):
        return out > 0
    return out


def histogram(values: jax.Array, num_bins: int,
              weights: jax.Array | None = None, *,
              into: jax.Array | None = None,
              impl: str | None = None, assume_exact: bool = False,
              block_e: int = 512, block_b: int = 128) -> jax.Array:
    """Weighted bincount of dictionary-encoded ``values`` (OOB dropped).

    ``weights=None`` counts occurrences (int32); bool/int weights produce
    int32 counts; float weights produce a float32 accumulation.  ``into``
    accumulates onto an existing (num_bins,) state — for float weights the
    XLA lowering scatters onto it in row order, which is what keeps chunked
    streaming bitwise identical to the whole-log pass.
    """
    if weights is None:
        w = jnp.ones(values.shape, jnp.int32)
    elif weights.dtype == jnp.bool_ or jnp.issubdtype(weights.dtype, jnp.integer):
        w = weights.astype(jnp.int32)
    else:
        w = weights.astype(jnp.float32)
    chosen = _resolve(impl, _inexact(w), assume_exact)
    if chosen == "pallas":
        # the VPU kernel accumulates in the weight dtype: int32 counting
        # stays exact at any magnitude (no float32 2^24 ceiling)
        out = histogram_pallas(values, w, num_bins,
                               block_e=block_e, block_b=block_b,
                               interpret=_interpret())
        return out if into is None else into + out
    if chosen == "xla":
        return _ref.histogram_ref(values, num_bins, w, into)
    raise ValueError(f"unknown histogram impl {chosen!r}")


def pair_count(src: jax.Array, dst: jax.Array, num_src: int,
               num_dst: int | None = None,
               weights: jax.Array | None = None, *,
               into: jax.Array | None = None,
               impl: str | None = None, assume_exact: bool = False,
               block_e: int = 512, block_s: int = 128,
               block_d: int = 128) -> jax.Array:
    """(num_src, num_dst) weighted (src, dst) pair counts (OOB dropped).

    The generalized DFG counter: ``impl`` may also name the XLA one-hot
    ``"matmul"`` lowering (MXU formulation without the Pallas runtime).
    ``into`` accumulates onto an existing state (row order on XLA — see
    ``histogram``).  The pallas/matmul lowerings accumulate in float32 on
    the MXU — exact while every *per-cell* sum stays < 2^24; for larger
    per-edge counts use the exact ``impl="xla"`` scatter.
    """
    num_dst = num_src if num_dst is None else num_dst
    if weights is None:
        w = jnp.ones(src.shape, jnp.int32)
    elif weights.dtype == jnp.bool_ or jnp.issubdtype(weights.dtype, jnp.integer):
        w = weights.astype(jnp.int32)
    else:
        w = weights.astype(jnp.float32)
    chosen = _resolve(impl, _inexact(w), assume_exact)
    if chosen == "pallas":
        out = pair_count_pallas(src, dst, w.astype(jnp.float32),
                                num_src, num_dst, block_e=block_e,
                                block_s=block_s, block_d=block_d,
                                interpret=_interpret()
                                ).astype(w.dtype)
        return out if into is None else into + out
    if chosen == "matmul":
        # the matmul lowering has its own tuned block size (2048), larger
        # than the Pallas event tile — don't forward block_e
        out = pair_count_matmul(src, dst, num_src, num_dst, weights=w)
        return out if into is None else into + out
    if chosen == "xla":
        return _ref.pair_count_ref(src, dst, w, num_src, num_dst, into)
    raise ValueError(f"unknown pair_count impl {chosen!r}")


def pair_count_matmul(src, dst, num_src, num_dst=None, weights=None, *,
                      block: int = 2048):
    """The XLA blockwise one-hot matmul lowering, callable directly."""
    num_dst = num_src if num_dst is None else num_dst
    w = jnp.ones(src.shape, jnp.int32) if weights is None else weights
    out = _ref.pair_count_matmul(src, dst, w.astype(jnp.float32),
                                 num_src, num_dst, block)
    if w.dtype != jnp.float32:
        return out.astype(jnp.int32)
    return out


def segmented_scan(values: jax.Array, seg_starts: jax.Array, carry,
                   op: str = "sum", *, base: int | None = None,
                   impl: str | None = None, assume_exact: bool = False,
                   block_e: int = 512):
    """Case-local inclusive scan; returns ``(ys, carry_out)``.

    ``op="sum"``: segmented prefix sum over (N,) or (N, K) rows, seeded by
    ``carry`` (the open segment's running total).  ``op="polyhash"``: the
    rolling hash ``h <- h*base + v`` (mod 2**32) over uint32 addends —
    exact, hence bitwise identical across lowerings.  ``carry_out`` is the
    inclusive value at the final row (feeds the next chunk's carry).
    """
    if op == "polyhash":
        if base is None:
            raise ValueError("segmented_scan(op='polyhash') requires base=")
        chosen = _resolve(impl, False, assume_exact)
        if chosen == "pallas":
            return segmented_polyhash_pallas(
                values, seg_starts, carry, int(base), block_e=block_e,
                interpret=_interpret())
        if chosen == "xla":
            return _ref.segmented_scan_ref(values, seg_starts, carry,
                                           "polyhash", base)
        raise ValueError(f"unknown segmented_scan impl {chosen!r}")
    if op == "sum":
        chosen = _resolve(impl, _inexact(values), assume_exact)
        if chosen == "pallas":
            return segmented_sum_scan_pallas(
                values, seg_starts, carry, block_e=block_e,
                interpret=_interpret())
        if chosen == "xla":
            return _ref.segmented_scan_ref(values, seg_starts, carry, "sum")
        raise ValueError(f"unknown segmented_scan impl {chosen!r}")
    raise ValueError(f"unknown segmented_scan op {op!r}")


def segmented_affine(mul: jax.Array, add: jax.Array, seg_starts: jax.Array,
                     carry, *, impl: str | None = None, block_e: int = 512):
    """Case-local scan of explicit affine maps ``h <- h*mul + add`` (mod
    2**32); returns ``(ys, carry_out)``.

    The generalization of ``segmented_scan(op="polyhash")`` where each row
    carries its own coefficients — what lets the variants kernel fold a
    pre-composed header *sketch* entry (the collapsed map of a whole skipped
    case run) in a single row.  uint32 arithmetic is exact mod 2^32, so both
    lowerings are bitwise identical.
    """
    chosen = _resolve(impl, False, False)
    if chosen == "pallas":
        return segmented_affine_pallas(mul, add, seg_starts, carry,
                                       block_e=block_e,
                                       interpret=_interpret())
    if chosen == "xla":
        return _ref.segmented_affine_ref(mul, add, seg_starts, carry)
    raise ValueError(f"unknown segmented_affine impl {chosen!r}")
