"""Jit'd dispatch wrapper for GQA decode attention.

``use_pallas`` routes between the Pallas flash-decode kernel (TPU target;
interpreted off the TPU) and the pure-jnp reference.  Model code calls this
entry point so the serving path picks the kernel up transparently.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.gqa_decode.kernel import gqa_decode_pallas
from repro.kernels.gqa_decode.ref import gqa_decode_ref


def gqa_decode(q, k, v, length, *, use_pallas=False):
    s = k.shape[1]
    if use_pallas and s % 128 == 0 and q.shape[-1] % 8 == 0:
        st = 256 if s % 256 == 0 else 128
        return gqa_decode_pallas(q, k, v, length, st=st)
    return gqa_decode_ref(q, k, v, length)
