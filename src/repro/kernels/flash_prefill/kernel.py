"""Pallas TPU kernel: causal / sliding-window GQA flash attention (prefill).

The S^2 logits never leave VMEM: grid (B, KV, Sq/QT, Sk/KT) with the KV
tile as the innermost (sequential) axis; a running online-softmax state
(m, l, acc) lives in VMEM scratch across KV tiles.  Causality and the SWA
window are enforced by position masks computed from the tile coordinates;
fully-masked tiles are skipped via pl.when on the tile bounds (a
(q_tile, k_tile) pair is dead if k_base > q_max or k_max <= q_min-window).

Block shapes: q (1, QT, G, D); k/v (1, KT, 1, D); QT=KT=256, D and the
G x KT MXU tiles are 128-aligned for hd=128 heads.  VMEM/program ~=
QT*G*D*4 (acc) + 2 tiles ~= 2-3 MiB at the defaults.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, qt: int, kt: int, scale: float, window: int, s: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_base = qi * qt
    k_base = ki * kt
    # live tile test: any (qp, kp) with kp <= qp and kp > qp - window?
    live = k_base <= q_base + qt - 1
    if window:
        live &= (k_base + kt - 1) > (q_base - window)

    @pl.when(live)
    def _work():
        q = q_ref[0, 0].astype(jnp.float32)             # (QT, G, D)
        k = k_ref[0, :, 0].astype(jnp.float32)          # (KT, D)
        v = v_ref[0, :, 0].astype(jnp.float32)
        g, d = q.shape[1], q.shape[2]
        logits = jax.lax.dot_general(
            q.reshape(qt * g, d), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (QT*G, KT)
        qp = q_base + jax.lax.broadcasted_iota(
            jnp.int32, (qt * g, kt), 0) // g
        kp = k_base + jax.lax.broadcasted_iota(jnp.int32, (qt * g, kt), 1)
        mask = kp <= qp
        if window:
            mask &= kp > qp - window
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        qshape = o_ref.shape                            # (1, QT, G, D)
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = out.reshape(qshape).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "qt", "kt", "interpret"))
def flash_prefill_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                         *, window: int = 0, qt: int = 256, kt: int = 256,
                         interpret: Optional[bool] = None) -> jax.Array:
    interpret = resolve_interpret(interpret)
    b, s, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    assert s % qt == 0 and s % kt == 0, (s, qt, kt)
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, s, kv, g, d).transpose(0, 2, 1, 3, 4)  # (B,KV,S,G,D)

    grid = (b, kv, s // qt, s // kt)
    out = pl.pallas_call(
        functools.partial(_kernel, qt=qt, kt=kt, scale=scale,
                          window=window, s=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, qt, g, d),
                         lambda bi, ni, qi, ki: (bi, ni, qi, 0, 0)),
            pl.BlockSpec((1, kt, 1, d),
                         lambda bi, ni, qi, ki: (bi, ki, ni, 0)),
            pl.BlockSpec((1, kt, 1, d),
                         lambda bi, ni, qi, ki: (bi, ki, ni, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qt, g, d),
                               lambda bi, ni, qi, ki: (bi, ni, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, s // qt * qt, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qt * g, 1), jnp.float32),
            pltpu.VMEM((qt * g, 1), jnp.float32),
            pltpu.VMEM((qt * g, d), jnp.float32),
        ],
        interpret=interpret,
    )(qg, k, v)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, s, h, d)
