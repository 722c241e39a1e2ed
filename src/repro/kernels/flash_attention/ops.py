"""Public entry point for fused attention; ``core.backend`` picks the
Pallas kernel or the reference oracle."""
from __future__ import annotations

from .flash_attention import flash_attention_pallas
from .ref import attention_ref


def flash_attention(q, k, v, kv_len=None, *, causal=True, window=None, impl=None):
    # deferred: repro.core's package init imports the kernel packages
    from repro.core import backend

    if backend.resolve(impl) == "pallas":
        return flash_attention_pallas(
            q, k, v, kv_len, causal=causal, window=window,
            interpret=backend.interpret_mode())
    return attention_ref(q, k, v, kv_len, causal=causal, window=window)
