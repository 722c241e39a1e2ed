"""Backend dispatch for every Pallas kernel (``kernels.*``), and the
persistent compile cache.

Every core algorithm's inner loop is one of four named columnar primitives
(``segment_reduce`` / ``histogram`` / ``pair_count`` / ``segmented_scan``),
and each primitive — like the graph semiring product and the DFG-count and
attention kernels — has two interchangeable lowerings:

* ``"pallas"`` — the Pallas TPU kernel (MXU one-hot matmul or VPU tiled
  reduction over the sorted stream).  On a TPU it is compiled by Mosaic;
  elsewhere the kernel body runs in interpret mode, which checks its
  arithmetic against the reference but not that the TPU's compiler
  accepts it — ``tests/test_tpu_compile.py`` compiles every kernel for a
  described TPU v5e for that.
* ``"xla"``    — the reference scatter/scan lowering (the paper's direct
  translation).  Row-order accumulation, used as the parity oracle and as
  the mandatory path for order-sensitive float accumulations.

Selection, most specific wins:

1. an explicit ``impl=`` argument at a primitive call site;
2. :func:`set_backend` / the :func:`use_backend` context manager;
3. the ``REPRO_SEGMENT_BACKEND`` environment variable (read at import);
4. ``"auto"``: pallas on TPU, xla elsewhere.

Backend choice is resolved when a kernel factory / primitive is *built*
(trace time).  The core factories include the resolved backend in their
cache keys, so ``use_backend("pallas")`` reliably rebuilds kernels inside
a process; plain jitted closures that dispatched at trace time keep their
original backend until retraced — CI therefore runs the pallas pass as a
separate process with ``REPRO_SEGMENT_BACKEND=pallas``.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax

ENV_VAR = "REPRO_SEGMENT_BACKEND"
BACKENDS = ("auto", "pallas", "xla")

_state = {"backend": os.environ.get(ENV_VAR, "auto")}


def get_backend() -> str:
    """The currently selected backend name (may be ``"auto"``)."""
    return _state["backend"]


def set_backend(name: str) -> None:
    if name not in BACKENDS:
        raise ValueError(f"unknown segment-ops backend {name!r}; "
                         f"expected one of {BACKENDS}")
    _state["backend"] = name


@contextlib.contextmanager
def use_backend(name: str):
    """Temporarily select a backend (tests: parity on both lowerings)."""
    prev = get_backend()
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


def resolve(impl: str | None = None, *, order_sensitive: bool = False,
            assume_exact: bool = False) -> str:
    """Concrete lowering for a primitive call: ``"pallas"`` or ``"xla"``.

    One guardrail for a lowering that was *selected* (no ``impl``): an
    order-sensitive accumulation — an inexact-float sum, whose rounding
    depends on the order a tiling adds in — takes the row-order XLA
    reference unless the caller asserts integer-valued operands with
    ``assume_exact=True``.  That keeps every lowering and every chunking
    bitwise equal.
    """
    b = impl if impl is not None else _state["backend"]
    if b == "auto":
        b = "pallas" if jax.default_backend() == "tpu" else "xla"
    elif b not in ("pallas", "xla"):
        raise ValueError(f"unknown segment-ops impl {b!r}")
    if impl is None and b == "pallas" and order_sensitive and not assume_exact:
        return "xla"
    return b


def interpret_mode() -> bool:
    """Pallas kernels run in interpret mode off-TPU (CPU CI validation)."""
    return jax.default_backend() != "tpu"


CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` — a fixed path, so a later run finds what an
    earlier one compiled.  Every compiled program is cached, however fast
    it compiled: a mining run compiles many small kernels, one per shape.
    Call before the first compilation (entry points only, never on import).
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = os.environ.get(CACHE_ENV_VAR)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
