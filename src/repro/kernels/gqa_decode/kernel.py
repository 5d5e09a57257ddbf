"""Pallas TPU kernel: flash-decode GQA attention (framework hot spot).

Decode attention is HBM-bandwidth bound (every step streams the whole KV
cache for one token of output).  The kernel tiles the cache sequence axis
through VMEM and keeps a numerically-stable online softmax accumulator
(running max m, normalizer l, weighted sum acc) in f32 VMEM scratch, so the
cache is read exactly once -- the roofline optimum for this op.

Grid: (B, KV, S / ST).  Block shapes: q (1, G, D) per (batch, kv-head);
k/v (1, ST, 1, D).  G = H / KV query heads share one KV head (GQA), so the
MXU operates on (G, D) @ (D, ST) tiles; D and ST are 128-multiples.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, st: int, scale: float):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (G, D)
    k = k_ref[0, :, 0].astype(jnp.float32)           # (ST, D)
    v = v_ref[0, :, 0].astype(jnp.float32)           # (ST, D)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (G, ST)
    pos = si * st + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(pos < len_ref[0], logits, NEG_INF)

    m_prev = m_ref[...]                               # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)                       # (G, ST)
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)     # (G, D)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(si == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("st", "interpret"))
def gqa_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                      length: jax.Array, *, st: int = 256,
                      interpret: Optional[bool] = None) -> jax.Array:
    """q f[B,H,D]; k,v f[B,S,KV,D]; length i32[B] -> f[B,H,D]."""
    interpret = resolve_interpret(interpret)
    b, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    assert h % kv == 0 and s % st == 0, (h, kv, s, st)
    scale = 1.0 / (d ** 0.5)

    qg = q.reshape(b, kv, g, d)
    grid = (b, kv, s // st)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, st=st, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, ni, si: (bi,)),            # length
            pl.BlockSpec((1, 1, g, d), lambda bi, ni, si: (bi, ni, 0, 0)),
            pl.BlockSpec((1, st, 1, d), lambda bi, ni, si: (bi, si, ni, 0)),
            pl.BlockSpec((1, st, 1, d), lambda bi, ni, si: (bi, si, ni, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bi, ni, si: (bi, ni, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),   # running max
            pltpu.VMEM((g, 1), jnp.float32),   # normalizer
            pltpu.VMEM((g, d), jnp.float32),   # weighted accumulator
        ],
        interpret=interpret,
    )(length, qg, k, v)
    return out.reshape(b, h, d)
