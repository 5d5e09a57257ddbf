"""Jit'd dispatch wrapper for flash prefill attention."""
from __future__ import annotations

from repro.kernels.flash_prefill.kernel import flash_prefill_pallas
from repro.kernels.flash_prefill.ref import flash_prefill_ref


def flash_prefill(q, k, v, *, window=0, use_pallas=False):
    s = q.shape[1]
    if use_pallas and s % 128 == 0:
        t = 256 if s % 256 == 0 else 128
        return flash_prefill_pallas(q, k, v, window=window, qt=t, kt=t)
    return flash_prefill_ref(q, k, v, window=window)
