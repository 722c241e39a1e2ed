"""Distributed variants: affine hash maps sharded over the data axis.

The rolling variant hash is a left fold, which looks sequential — but
every row of the stream is an *affine map* ``h -> h*m + b`` over uint32
(real rows: ``(BASE, act+1)``; ghost rows from pruned scans: the
composed per-segment sketch maps of ``core.polyhash``; padding rows:
the identity).  Affine maps compose associatively, so the fold shards:

1. each shard runs the segmented affine scan twice, seeded with ``h=0``
   and ``h=1`` — the two evaluations of an affine function recover its
   coefficients, ``ys(h) = mr*h + ys0`` with ``mr = ys1 - ys0`` (``mr``
   self-zeroes at the first segment restart inside the shard, because
   the restart severs the dependence on the incoming carry);
2. one ``all_gather`` of each shard's whole-shard map
   ``(mr[-1], ys0[-1])`` (payload: 2 uint32 per shard per base) and an
   O(shards) fold give every shard its true incoming carry — no halo
   depth constraint, any shard may hold less than a case;
3. per-row hashes ``mr*h_in + ys0``; each case's hash at its end row is
   scattered by global segment id (``segment_reduce``) and one ``psum``
   assembles the replicated fingerprint table (every end row lives on
   exactly one shard, so the sum has one nonzero contribution per case).

Bitwise equal to the streaming ``variants_kernel`` and the whole-log
``variant_fingerprints``: uint32 arithmetic is exact mod 2^32 under both
backends, and the composition order is the stream order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.segment_ops import segment_reduce, segmented_affine


def _varying(x, axis_name):
    """A per-shard seed: scan carries must vary over the mesh axis as the
    shard data they fold does (``shard_map``'s varying-axes check)."""
    return jax.lax.pcast(x, axis_name, to="varying")


def _base_fingerprints(m, b, starts, seg, ends, num_cases, *, axis_name,
                       n_dev):
    ys0, _ = segmented_affine(m, b, starts, _varying(jnp.uint32(0), axis_name))
    ys1, _ = segmented_affine(m, b, starts, _varying(jnp.uint32(1), axis_name))
    mr = ys1 - ys0              # shard-prefix map slope (0 after a restart)
    gather = jax.lax.all_gather(jnp.stack([mr[-1], ys0[-1]]), axis_name)
    idx = jax.lax.axis_index(axis_name)

    def fold(h, i):             # compose the preceding shards' maps, in order
        return jnp.where(i < idx, h * gather[i, 0] + gather[i, 1], h), None

    h_in, _ = jax.lax.scan(fold, _varying(jnp.uint32(0), axis_name),
                           jnp.arange(n_dev))
    hs = mr * h_in + ys0        # exact per-row hashes given the true carry
    fp = segment_reduce(jnp.where(ends, hs, jnp.uint32(0)), seg, num_cases,
                        "max")
    return jax.lax.psum(fp, axis_name)


def run_sharded_variants(m1, b1, m2, b2, starts, seg, ends, num_cases: int,
                         *, axis_name, n_dev):
    """Shard-local driver: per-case ``(fp1, fp2)`` fingerprint tables,
    replicated.  ``starts``/``seg``/``ends`` are the *global* segment
    markers (host-derived from the padded case column) sliced per shard."""
    fp1 = _base_fingerprints(m1, b1, starts, seg, ends, num_cases,
                             axis_name=axis_name, n_dev=n_dev)
    fp2 = _base_fingerprints(m2, b2, starts, seg, ends, num_cases,
                             axis_name=axis_name, n_dev=n_dev)
    return fp1, fp2
