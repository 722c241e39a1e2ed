"""Spans and counters of the mining path.

A *record* holds the counters of one scan (or of one service request):

* ``timings`` — ``{span name: (count, total_s, self_s)}``.  Self time is
  a span's duration less the time its child spans on the same thread
  cover;
* ``host_syncs`` — device-to-host pulls made through :func:`pull`;
* ``bytes_to_device`` — bytes a decoded row group put on the device
  (:func:`put`);
* ``compiles`` — ``{fun_name: count}``: programs compiled or loaded from
  the persistent compile cache (JAX's ``backend_compile_duration`` event
  wraps both), charged to the record bound on the thread that compiled.

A record is bound per thread (:func:`record`, :func:`bind`); the
read-ahead worker binds its scan's record explicitly.  The scan entry
points of ``repro.query.exec`` add their record into the ``ScanReport``
they return, so every report — and every service response — carries
them.

:class:`span` also opens ``jax.profiler.TraceAnnotation("repro.<name>")``,
so a running ``jax.profiler`` trace shows each span on the host, on the
same clock as the device's ops, with ``scan=<record id>`` and, on
per-group spans, ``group=<index>``.  With no trace recording a span opens
no annotation: it costs two clock reads and a dictionary update.  Keep
spans out of per-row and per-event loops.
"""
from __future__ import annotations

import itertools
import threading
import time
from contextlib import contextmanager

import jax
import numpy as np

PREFIX = "repro."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_Annotation = jax.profiler.TraceAnnotation
_clock = time.perf_counter
_ids = itertools.count(1)
_lock = threading.Lock()          # records are shared with the read-ahead


class _Thread(threading.local):
    """Per thread: the bound record, and the child seconds of each open
    span (innermost last)."""

    record = None

    def __init__(self):
        self.stack = []


_tls = _Thread()


class Record:
    """The counters of one scan or request (module docstring)."""

    __slots__ = ("id", "timings", "host_syncs", "bytes_to_device",
                 "compiles")

    def __init__(self):
        self.id = next(_ids)
        self.timings: dict = {}
        self.host_syncs = 0
        self.bytes_to_device = 0
        self.compiles: dict = {}


def current() -> Record | None:
    """The record bound on this thread, or None."""
    return _tls.record


@contextmanager
def bind(rec: Record | None):
    """Bind ``rec`` on this thread for the block (how the read-ahead worker
    serves its scan's record)."""
    prev = current()
    _tls.record = rec
    try:
        yield rec
    finally:
        _tls.record = prev


@contextmanager
def record():
    """A fresh record bound on this thread.  On exit its counters are added
    into the record it was nested in, so a request's record holds its
    scans'."""
    rec = Record()
    outer = current()
    try:
        with bind(rec):
            yield rec
    finally:
        if outer is not None:
            add(outer, rec)


def add(dst, src) -> None:
    """Add ``src``'s counters into ``dst`` (a :class:`Record` or a
    ``ScanReport``)."""
    with _lock:
        for name, (n, total, own) in src.timings.items():
            c, t, s = dst.timings.get(name, (0, 0.0, 0.0))
            dst.timings[name] = (c + n, t + total, s + own)
        dst.host_syncs += src.host_syncs
        dst.bytes_to_device += src.bytes_to_device
        for name, n in src.compiles.items():
            dst.compiles[name] = dst.compiles.get(name, 0) + n


class span:
    """``with span("edf.decode", group=g):`` — time the block into the
    bound record and mark it in the profiler's trace as ``repro.<name>``.
    A span must not stay open across a generator's ``yield``: its
    consumer's spans would count as its children."""

    __slots__ = ("name", "rec", "ann", "t0")

    def __init__(self, name: str, **meta):
        self.name = name
        self.rec = _tls.record
        self.ann = None
        if _Annotation.is_enabled():        # a profiler trace is recording
            if self.rec is not None:
                meta["scan"] = self.rec.id
            self.ann = _Annotation(PREFIX + name, **meta)

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        _tls.stack.append(0.0)
        self.t0 = _clock()
        return self

    def __exit__(self, *exc):
        dt = _clock() - self.t0
        stack = _tls.stack
        child = stack.pop()
        if stack:
            stack[-1] += dt
        if self.ann is not None:
            self.ann.__exit__(*exc)
        rec = self.rec
        if rec is not None:
            with _lock:
                c, t, s = rec.timings.get(self.name, (0, 0.0, 0.0))
                rec.timings[self.name] = (c + 1, t + dt, s + dt - child)
        return False


def pull(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``, counting a device-to-host sync in the
    bound record when ``x`` lives on the device."""
    if isinstance(x, jax.Array):
        rec = current()
        if rec is not None:
            with _lock:
                rec.host_syncs += 1
    return np.asarray(x, dtype)


def put(nbytes: int) -> None:
    """Count ``nbytes`` put on the device in the bound record."""
    rec = current()
    if rec is not None:
        with _lock:
            rec.bytes_to_device += int(nbytes)


def _on_duration(event: str, duration: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    rec = current()
    if rec is not None:
        name = str(kw.get("fun_name", "?"))
        with _lock:
            rec.compiles[name] = rec.compiles.get(name, 0) + 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
