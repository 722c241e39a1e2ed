"""The trace reduction on hand-built traces: busy and idle by the union
of device intervals, per-kernel sums, roofline arithmetic, collective
share, idle gaps by host span, and the table of peaks."""
import math

import pytest

from bench import peaks, trace, work

V5E = peaks.peaks("TPU v5 lite")
PAIR = ("%pair_count_pallas.1 = f32[32,128]{1,0:T(8,128)S(1)} custom-call("
        "s32[1,1024]{1,0:T(1,128)S(1)} %a, s32[1,1024]{1,0:T(1,128)S(1)} %b,"
        " f32[1,1024]{1,0:T(1,128)S(1)} %w), custom_call_target="
        "\"tpu_custom_call\"")
SEGRED = ("%segment_reduce_pallas = s32[64,128]{1,0} custom-call(s32[8]{0} "
          "%f, s32[8]{0} %l, s32[1,1024]{1,0} %seg, s32[1,1024]{1,0} %val, "
          "s32[64,128]{1,0} %init)")
FUSION = "%fusion.3 = f32[27]{0} fusion(f32[27]{0} %p, s32[100]{0} %q)"
GATHER = "%all-gather.2 = s32[4096]{0} all-gather(s32[1024]{0} %x)"


def make(devices, spans=()):
    return trace.Trace.from_events(
        devices, [(0, 1000, "bench.window", "main")] + list(spans))


def test_busy_is_the_union_of_nested_and_overlapping_ops():
    tr = make({"/device:TPU:0": [(100, 300, FUSION), (150, 250, PAIR),
                                 (280, 400, FUSION), (900, 1200, FUSION)]})
    # [100, 400] and [900, 1000] inside the window
    assert trace.busy_s(tr) == pytest.approx(400e-9)
    assert trace.idle_pct(tr) == pytest.approx(60.0)


def test_busy_is_the_mean_over_devices():
    tr = make({"/device:TPU:0": [(0, 500, FUSION)],
               "/device:TPU:1": [(0, 100, FUSION)]})
    assert trace.busy_s(tr) == pytest.approx(300e-9)


def test_ops_outside_the_window_do_not_count():
    tr = make({"/device:TPU:0": [(-500, -100, FUSION), (1100, 1500, PAIR)]})
    assert trace.busy_s(tr) == 0.0
    assert trace.kernel_roofline_pct(tr, V5E) is None


def test_per_kernel_sums():
    tr = make({"/device:TPU:0": [(0, 100, PAIR), (200, 260, PAIR),
                                 (300, 400, FUSION)],
               "/device:TPU:1": [(0, 40, PAIR)]})
    secs = trace.op_seconds(tr)
    assert secs["pair_count_pallas"] == pytest.approx(200e-9)
    assert secs["fusion"] == pytest.approx(100e-9)
    ops = dict(trace.device_ops(tr))
    assert max(ops.values()) == pytest.approx(200e-9)


def test_work_from_recorded_shapes():
    ops, nbytes = work.work(PAIR)
    assert ops == 1024
    assert nbytes == 3 * 1024 * 4 + 32 * 128 * 4
    # segment_reduce: the aliased identity input does not count, and at
    # most one output element per event is written
    ops, nbytes = work.work(SEGRED)
    assert ops == 1024
    assert nbytes == 2 * 8 * 4 + 2 * 1024 * 4 + 1024 * 4
    assert work.work(FUSION) is None


def test_roofline_arithmetic():
    dur_ns = 1000
    tr = make({"/device:TPU:0": [(0, dur_ns, PAIR)]})
    ops, nbytes = work.work(PAIR)
    want = max(nbytes / V5E["hbm_bytes_per_s"], ops / V5E["int8_ops_per_s"])
    assert trace.kernel_roofline_pct(tr, V5E) == pytest.approx(
        100 * want / (dur_ns * 1e-9))
    # only the mining kernels enter: a fusion beside them changes nothing
    tr2 = make({"/device:TPU:0": [(0, dur_ns, PAIR), (0, 500, FUSION)]})
    assert trace.kernel_roofline_pct(tr2, V5E) == pytest.approx(
        trace.kernel_roofline_pct(tr, V5E))


def test_semiring_work():
    hlo = ("%semiring_matmul_pallas.1 = f32[128,128]{1,0} custom-call("
           "f32[128,128]{1,0} %a, f32[128,128]{1,0} %b)")
    ops, nbytes = work.work(hlo)
    assert ops == 2 * 128 ** 3
    assert nbytes == 3 * 128 * 128 * 4


def test_collective_share():
    tr = make({"/device:TPU:0": [(0, 100, GATHER), (100, 400, FUSION)],
               "/device:TPU:1": [(0, 100, GATHER)]})
    assert trace.collective_pct(tr) == pytest.approx(40.0)
    assert trace.collective_pct(make({})) is None


def test_idle_gaps_by_host_span():
    spans = [(0, 500, "bench.mine", "main"), (500, 1000, "bench.ingest",
                                              "ingest")]
    tr = make({"/device:TPU:0": [(0, 100, FUSION), (400, 600, FUSION)]},
              spans)
    gaps = dict(trace.idle_gaps(tr))
    assert gaps["bench.mine"] == pytest.approx(300e-9)
    assert gaps["bench.ingest"] == pytest.approx(400e-9)


def test_a_trace_without_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.Trace.from_events({}, [])


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    assert math.isclose(V5E["hbm_bytes_per_s"], 819e9)
