"""Segmented-primitive layer: Pallas (interpret) == XLA bitwise parity,
backend dispatch rules, and end-to-end algorithm equivalence on the pallas
backend.  This file is the CPU-only CI gate for kernel regressions."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import ACTIVITY, CASE, TIMESTAMP, ChunkedEventFrame, backend
from repro.core import run_streaming, stats, variants
from repro.core.dfg import dfg_kernel, dfg_segment
from repro.core.performance import eventually_follows, eventually_follows_kernel
from repro.kernels import segment_ops as so

from helpers import random_log, sorted_frame

rng = np.random.default_rng(7)


def _consecutive_sorted_ids(n, approx_segments):
    seg = np.sort(rng.integers(0, approx_segments, n)).astype(np.int32)
    if n:
        seg = (np.cumsum(np.concatenate([[1], np.diff(seg) != 0])) - 1).astype(np.int32)
    return seg


# ------------------------------------------------------------ parity: bitwise
@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("n,block", [(1, 128), (300, 64), (1000, 128), (513, 512)])
def test_segment_reduce_parity(op, n, block):
    seg = _consecutive_sorted_ids(n, max(n // 7, 2))
    s = int(seg.max()) + 1 if n else 1
    vals = jnp.asarray(rng.integers(-50, 50, n), jnp.int32)
    a = so.segment_reduce(vals, jnp.asarray(seg), s, op, impl="xla")
    b = so.segment_reduce(vals, jnp.asarray(seg), s, op, impl="pallas",
                          block_e=block)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_segment_reduce_drops_out_of_range():
    seg = _consecutive_sorted_ids(400, 40)
    s = int(seg.max()) + 1
    seg[:7] = -1            # the engine's pre-first-row carry id
    seg[-7:] = s + 1000     # beyond the configured capacity
    vals = jnp.asarray(rng.integers(0, 9, 400), jnp.int32)
    a = so.segment_reduce(vals, jnp.asarray(seg), s, "sum", impl="xla")
    b = so.segment_reduce(vals, jnp.asarray(seg), s, "sum", impl="pallas",
                          block_e=128)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(a).sum()) == int(np.asarray(vals)[7:-7].sum())


def test_segment_reduce_float_minmax_and_bool():
    seg = _consecutive_sorted_ids(500, 30)
    s = int(seg.max()) + 1
    ts = jnp.asarray(rng.random(500) * 1e6, jnp.float32)
    for op in ("min", "max"):
        a = so.segment_reduce(ts, jnp.asarray(seg), s, op, impl="xla")
        b = so.segment_reduce(ts, jnp.asarray(seg), s, op, impl="pallas",
                              block_e=128)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    hit = jnp.asarray(rng.random(500) < 0.2)
    a = so.segment_reduce(hit, jnp.asarray(seg), s, "max", impl="xla")
    b = so.segment_reduce(hit, jnp.asarray(seg), s, "max", impl="pallas",
                          block_e=128)
    assert a.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("nbins,n,blocks", [(5, 1000, (128, 32)),
                                            (48, 777, (256, 128)),
                                            (300, 1000, (128, 64)),
                                            (7, 1, (512, 128))])
def test_histogram_parity(nbins, n, blocks):
    v = jnp.asarray(rng.integers(-2, nbins + 3, n), jnp.int32)
    w = jnp.asarray(rng.integers(0, 5, n), jnp.int32)
    be, bb = blocks
    for weights in (None, w):
        a = so.histogram(v, nbins, weights, impl="xla")
        b = so.histogram(v, nbins, weights, impl="pallas", block_e=be, block_b=bb)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_histogram_into_accumulates():
    v = jnp.asarray(rng.integers(0, 6, 100), jnp.int32)
    prev = jnp.asarray(rng.integers(0, 9, 6), jnp.int32)
    out = so.histogram(v, 6, into=prev, impl="xla")
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(prev) + np.asarray(so.histogram(v, 6, impl="xla")))


@pytest.mark.parametrize("ns,nd,n", [(11, 7, 1000), (130, 130, 2000), (3, 200, 500)])
def test_pair_count_parity_three_lowerings(ns, nd, n):
    s = jnp.asarray(rng.integers(-1, ns + 1, n), jnp.int32)
    d = jnp.asarray(rng.integers(-1, nd + 1, n), jnp.int32)
    m = jnp.asarray(rng.random(n) < 0.7)
    ref = np.asarray(so.pair_count(s, d, ns, nd, m, impl="xla"))
    for impl in ("matmul", "pallas"):
        got = so.pair_count(s, d, ns, nd, m, impl=impl, block_e=256)
        np.testing.assert_array_equal(np.asarray(got), ref, err_msg=impl)


@pytest.mark.parametrize("n,block", [(64, 64), (1000, 64), (513, 256), (1, 128)])
def test_segmented_polyhash_parity(n, block):
    acts = jnp.asarray(rng.integers(1, 30, n), jnp.uint32)
    starts = np.asarray(rng.random(n) < 0.2)
    starts[0] = True
    h0 = jnp.uint32(rng.integers(0, 2**31))
    a_ys, a_c = so.segmented_scan(acts, jnp.asarray(starts), h0, "polyhash",
                                  base=1_000_003, impl="xla")
    b_ys, b_c = so.segmented_scan(acts, jnp.asarray(starts), h0, "polyhash",
                                  base=1_000_003, impl="pallas", block_e=block)
    np.testing.assert_array_equal(np.asarray(a_ys), np.asarray(b_ys))
    assert int(a_c) == int(b_c)


@pytest.mark.parametrize("n,block", [(64, 64), (1000, 64), (513, 256), (1, 128)])
def test_segmented_affine_parity(n, block):
    """Per-row (mul, add) affine scan: pallas == xla bitwise, carry and
    all — the primitive under sketch-folding variants."""
    mul = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    add = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    starts = np.asarray(rng.random(n) < 0.2)
    starts[0] = True
    h0 = jnp.uint32(rng.integers(0, 2**31))
    a_ys, a_c = so.segmented_affine(mul, add, jnp.asarray(starts), h0,
                                    impl="xla")
    b_ys, b_c = so.segmented_affine(mul, add, jnp.asarray(starts), h0,
                                    impl="pallas", block_e=block)
    np.testing.assert_array_equal(np.asarray(a_ys), np.asarray(b_ys))
    assert int(a_c) == int(b_c)
    # degenerate polyhash: mul == BASE, add == token reproduces the
    # polyhash scan exactly
    acts = jnp.asarray(rng.integers(1, 30, n), jnp.uint32)
    p_ys, p_c = so.segmented_scan(acts, jnp.asarray(starts), jnp.uint32(0),
                                  "polyhash", base=1_000_003, impl="xla")
    e_ys, e_c = so.segmented_affine(jnp.full(n, 1_000_003, jnp.uint32),
                                    acts, jnp.asarray(starts), jnp.uint32(0),
                                    impl="xla")
    np.testing.assert_array_equal(np.asarray(p_ys), np.asarray(e_ys))
    assert int(p_c) == int(e_c)


@pytest.mark.parametrize("k", [1, 6])
def test_segmented_sum_scan_parity(k):
    n = 700
    oh = np.eye(k, dtype=np.float32)[rng.integers(0, k, n)]
    starts = np.asarray(rng.random(n) < 0.15)
    carry = rng.integers(0, 4, k).astype(np.float32)
    a_ys, a_c = so.segmented_scan(jnp.asarray(oh), jnp.asarray(starts),
                                  jnp.asarray(carry), "sum", impl="xla")
    b_ys, b_c = so.segmented_scan(jnp.asarray(oh), jnp.asarray(starts),
                                  jnp.asarray(carry), "sum", impl="pallas",
                                  block_e=128)
    np.testing.assert_array_equal(np.asarray(a_ys), np.asarray(b_ys))
    np.testing.assert_array_equal(np.asarray(a_c), np.asarray(b_c))


def test_scan_carry_chains_across_chunks():
    """Seeding a scan with the previous chunk's carry_out reproduces the
    whole-stream scan — the streaming engine's stitching property, at the
    primitive level, on both lowerings."""
    n, cut = 900, 391
    acts = jnp.asarray(rng.integers(1, 9, n), jnp.uint32)
    starts = np.asarray(rng.random(n) < 0.2)
    starts[0] = True
    whole, cw = so.segmented_scan(acts, jnp.asarray(starts), jnp.uint32(0),
                                  "polyhash", base=257, impl="xla")
    for impl in ("xla", "pallas"):
        y1, c1 = so.segmented_scan(acts[:cut], jnp.asarray(starts[:cut]),
                                   jnp.uint32(0), "polyhash", base=257,
                                   impl=impl, block_e=128)
        y2, c2 = so.segmented_scan(acts[cut:], jnp.asarray(starts[cut:]),
                                   c1, "polyhash", base=257,
                                   impl=impl, block_e=128)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(y1), np.asarray(y2)]), np.asarray(whole))
        assert int(c2) == int(cw)


# ------------------------------------------------------- dispatch semantics
def test_backend_dispatch_and_float_gate():
    assert backend.resolve("pallas") == "pallas"
    assert backend.resolve("xla") == "xla"
    with backend.use_backend("pallas"):
        assert backend.resolve(order_sensitive=True) == "xla"
        assert backend.resolve(order_sensitive=True,
                               assume_exact=True) == "pallas"
        assert backend.resolve("pallas", order_sensitive=True) == "pallas"
    with backend.use_backend("xla"):
        assert backend.get_backend() == "xla"
    with pytest.raises(ValueError):
        backend.set_backend("cuda")
    # float-weighted accumulation is order-sensitive: under the pallas
    # backend it must still take the row-order XLA scatter by default
    n = 1000
    v = jnp.asarray(rng.integers(0, 8, n), jnp.int32)
    w = jnp.asarray(rng.random(n), jnp.float32)
    with backend.use_backend("pallas"):
        gated = so.histogram(v, 8, w)
    np.testing.assert_array_equal(np.asarray(gated),
                                  np.asarray(so.histogram(v, 8, w, impl="xla")))


def test_mergstrv_int32_overflow_guard():
    from repro.core import EventFrame, ops

    frame = EventFrame.from_numpy({
        "a": np.asarray([1, 2**16], np.int32),
        "b": np.asarray([3, 4], np.int32),
    })
    with pytest.raises(OverflowError, match="int32"):
        ops.mergstrv(frame, "m", "a", "b", 2**16)
    # in-range encodings still work and stay injective
    small = EventFrame.from_numpy({
        "a": np.asarray([1, 2000], np.int32),
        "b": np.asarray([3, 4], np.int32),
    })
    out = ops.mergstrv(small, "m", "a", "b", 2**16)
    assert int(out["m"][0]) == 2**16 + 3
    assert int(out["m"][1]) == 2000 * 2**16 + 4


# ------------------------------------------- end-to-end on the pallas backend
def _small_frame(seed=3):
    r = np.random.default_rng(seed)
    log = random_log(r, n_cases=18, n_acts=5, max_len=7)
    frame, tables = sorted_frame(log)
    return log, frame, len(tables[ACTIVITY])


def test_dfg_streaming_invariance_on_pallas_backend():
    log, frame, a = _small_frame()
    ref = dfg_segment(frame, a)          # XLA scatter whole-log oracle
    src = ChunkedEventFrame.from_frame(frame, 29)
    with backend.use_backend("pallas"):
        got = run_streaming(dfg_kernel(a), src)
    for nm in ("counts", "starts", "ends"):
        np.testing.assert_array_equal(np.asarray(getattr(got, nm)),
                                      np.asarray(getattr(ref, nm)), err_msg=nm)


def test_stats_variants_efg_on_pallas_backend():
    log, frame, a = _small_frame(11)
    c = len(log.case_ids)
    src = ChunkedEventFrame.from_frame(frame, 23)
    ref_sizes = np.asarray(stats.case_sizes(frame, c))
    ref_dur = np.asarray(stats.case_durations(frame, c))
    ref_var = variants.variant_counts(frame)
    ref_efg = np.asarray(eventually_follows(frame, a))
    with backend.use_backend("pallas"):
        np.testing.assert_array_equal(
            np.asarray(run_streaming(stats.case_sizes_kernel(c), src)), ref_sizes)
        np.testing.assert_array_equal(
            np.asarray(run_streaming(stats.case_durations_kernel(c), src)), ref_dur)
        assert variants.streaming_variant_counts(src, c) == ref_var
        np.testing.assert_array_equal(
            np.asarray(run_streaming(eventually_follows_kernel(a), src)), ref_efg)