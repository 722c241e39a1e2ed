"""The reduction from a profiler trace to the benchmark's device numbers.

A trace here is what ``Trace.load`` takes from the ``.xplane.pb`` that
``jax.profiler`` writes: for each device plane (``/device:TPU:<n>``) the
events of its ``XLA Ops`` line, each an interval with the op's HLO text as
its name; and the host events, among them the benchmark's own spans
(``bench.*``, written with ``jax.profiler.TraceAnnotation``).  Device and
host events share the profiler's clock.  Everything below is a pure
function of those intervals, so tests can hand-build a trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

from . import work

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|collective-permute|"
                        r"reduce-scatter|all-to-all|collective-broadcast)"
                        r"(-start|-done)?$")


@dataclasses.dataclass
class Trace:
    """Device op intervals per device and host spans, in nanoseconds."""

    devices: dict           # device plane name -> [(start, end, hlo)]
    spans: list             # [(start, end, name, thread)]
    window: tuple           # (start, end) of the measured window

    @classmethod
    def load(cls, log_dir: str) -> "Trace":
        from jax.profiler import ProfileData

        files = sorted(glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        devices, spans = {}, []
        for plane in ProfileData.from_file(files[-1]).planes:
            if plane.name.startswith("/device:TPU:"):
                ops = []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops += [(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events]
                devices[plane.name] = ops
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            spans.append((e.start_ns,
                                          e.start_ns + e.duration_ns,
                                          e.name, line.name))
        return cls.from_events(devices, spans)

    @classmethod
    def from_events(cls, devices: dict, spans: list) -> "Trace":
        win = [s for s in spans if s[2] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        return cls(devices, spans, (win[0][0], win[0][1]))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def union(intervals) -> list:
    """Merged (start, end) of possibly overlapping or nested intervals."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which an op ran, mean over the devices of the trace."""
    if not trace.devices:
        return 0.0
    lo, hi = trace.window
    tot = sum(sum(e - s for s, e in union(clip(ops, lo, hi)))
              for ops in trace.devices.values())
    return tot * 1e-9 / len(trace.devices)


def idle_pct(trace: Trace) -> float:
    return 100.0 * (1.0 - busy_s(trace) / trace.window_s)


def op_seconds(trace: Trace, select=None) -> dict:
    """Device seconds per op name (``work.op_name``) inside the window,
    summed over devices; ``select(hlo)`` filters the ops."""
    lo, hi = trace.window
    out = {}
    for ops in trace.devices.values():
        for s, e, hlo in clip(ops, lo, hi):
            if select is None or select(hlo):
                name = work.op_name(hlo)
                out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out


def kernel_roofline_pct(trace: Trace, peak: dict) -> float | None:
    """Sum of roofline times over sum of device times of the mining
    kernels in the window; None where none ran."""
    lo, hi = trace.window
    t_dev = t_roof = 0.0
    for ops in trace.devices.values():
        for s, e, hlo in clip(ops, lo, hi):
            r = work.roofline_s(hlo, peak)
            if r is not None:
                t_dev += (e - s) * 1e-9
                t_roof += r
    return 100.0 * t_roof / t_dev if t_dev > 0 else None


def collective_pct(trace: Trace) -> float | None:
    """Share of device time in collective ops; None with no device time."""
    times = op_seconds(trace)
    total = sum(times.values())
    if total <= 0:
        return None
    coll = sum(v for k, v in times.items() if COLLECTIVE.match(k))
    return 100.0 * coll / total


def device_ops(trace: Trace, top: int = 10) -> list:
    """The ops that took the most device time: [[name, seconds], ...]."""
    lo, hi = trace.window
    out = {}
    for ops in trace.devices.values():
        for s, e, hlo in clip(ops, lo, hi):
            head = hlo.split(" custom-call(")[0].split(" fusion(")[0]
            name = work.op_name(hlo) + " " + head.partition(" = ")[2][:60]
            out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])
            [:top]]


def idle_gaps(trace: Trace, top: int = 10) -> list:
    """Idle device seconds inside the window, by the innermost benchmark
    span the host was in at each gap's middle: [[span, seconds], ...]."""
    lo, hi = trace.window
    spans = sorted(s for s in trace.spans if s[2] != WINDOW_SPAN)
    out = {}
    for ops in trace.devices.values():
        busy = union(clip(ops, lo, hi))
        edges = [lo] + [x for se in busy for x in se] + [hi]
        gaps = sorted(((a + b) / 2, b - a) for a, b in
                      zip(edges[0::2], edges[1::2]) if b > a)
        active, nxt = [], 0
        for mid, length in gaps:        # one sweep over gaps and spans
            while nxt < len(spans) and spans[nxt][0] <= mid:
                active.append(spans[nxt])
                nxt += 1
            active = [s for s in active if s[1] > mid]
            name = (min(active, key=lambda s: s[1] - s[0])[2] if active
                    else "outside any span")
            out[name] = out.get(name, 0.0) + length * 1e-9
    n = max(len(trace.devices), 1)
    return [[k, v / n] for k, v in sorted(out.items(), key=lambda kv: -kv[1])
            [:top]]
