"""Shared neural building blocks: norms, rotary embeddings, attention
(train / prefill / decode with GQA, MLA, SWA), SwiGLU MLP.

All functions are pure; params are dict subtrees produced by params.py.
Compute dtype follows the config; accumulation / softmax / norms in f32.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.sharding import ShardCtx

NEG_INF = -1e30


def _wsc(x, spec, mesh):
    """with_sharding_constraint via an explicit NamedSharding (jax 0.8 has
    no ambient mesh, so raw PartitionSpecs would be rejected)."""
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, (tuple, list)):
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axes]


def constrain(ctx: ShardCtx, x, *roles):
    """Sharding constraint by role; silently drops axes whose mesh size does
    not divide the corresponding dim (uneven constraints confuse GSPMD)."""
    if not ctx.enabled:
        return x
    from repro.launch.meshctx import get_mesh
    mesh = get_mesh()
    if mesh is None:
        return x
    axes = []
    for dim, r in zip(x.shape, roles):
        if r == "dp":
            a = ctx.dp()
        elif r == "tp":
            a = ctx.tp()
        elif r == "sp":
            a = ctx.tp() if ctx.sp_activations else None
        else:
            a = None
        if a is not None and dim % _axis_size(mesh, a) != 0:
            a = None
        axes.append(a)
    return _wsc(x, P(*axes), mesh)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * lax.rsqrt(var + cfg.norm_eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * lax.rsqrt(ms + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def head_rms(scale: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """qk-norm: RMS over the head dim (qwen3)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------

def _rope_angles(positions: jax.Array, dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions (..., S) -> cos/sin (..., S, dim/2) in f32."""
    half = dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: Optional[Tuple[int, int, int]] = None) -> jax.Array:
    """x (B, S, H, D); positions (B, S) or (3, B, S) for M-RoPE."""
    d = x.shape[-1]
    half = d // 2
    if mrope_sections is not None and positions.ndim == 3:
        cos_p, sin_p = _rope_angles(positions, d, theta)     # (3, B, S, half)
        secs = mrope_sections
        assert sum(secs) == half, (secs, half)
        parts_c, parts_s = [], []
        off = 0
        for i, s in enumerate(secs):
            parts_c.append(cos_p[i, ..., off:off + s])
            parts_s.append(sin_p[i, ..., off:off + s])
            off += s
        cos = jnp.concatenate(parts_c, -1)
        sin = jnp.concatenate(parts_s, -1)
    else:
        cos, sin = _rope_angles(positions, d, theta)          # (B, S, half)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention cores
# ---------------------------------------------------------------------------

def _grouped_logits(q: jax.Array, k: jax.Array) -> jax.Array:
    """q (B,Sq,KV,G,D) x k (B,Sk,KV,D) -> (B,KV,G,Sq,Sk) f32 logits."""
    return jnp.einsum("bqngd,bknd->bngqk",
                      q.astype(jnp.float32), k.astype(jnp.float32))


def attention_dense(ctx: ShardCtx, q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, k_pos: jax.Array,
                    window: Optional[int], causal: bool = True,
                    q_chunk: int = 512) -> jax.Array:
    """Memory-chunked multi-query attention for train / prefill.

    q (B,Sq,H,D); k,v (B,Sk,KV,D); positions are absolute per token
    (B, S).  Chunking over Sq bounds the live logits to (B,KV,G,qc,Sk).
    """
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, kv, g, d)

    def chunk_fn(args):
        qc, qpc = args                                   # (B,C,KV,G,D), (B,C)
        logits = _grouped_logits(qc, k) * scale          # (B,KV,G,C,Sk)
        mask = jnp.ones((b, qc.shape[1], sk), jnp.bool_)
        if causal:
            mask &= k_pos[:, None, :] <= qpc[:, :, None]
        if window:
            mask &= k_pos[:, None, :] > qpc[:, :, None] - window
        logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
        # softmax in f32, then cast p to the compute dtype: the (.., C, Sk)
        # probability tensor dominates attention's HBM bytes at long S and
        # the MXU consumes bf16 anyway (§Perf iteration: -~2x on that read)
        p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bngqk,bknd->bqngd", p, v,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, qc.shape[1], h, dv)

    if sq <= q_chunk:
        return chunk_fn((qg, q_pos)).astype(q.dtype)
    while sq % q_chunk:
        q_chunk -= 1          # largest divisor (e.g. whisper's 1500 -> 500)
    nc = sq // q_chunk
    qs = qg.reshape(b, nc, q_chunk, kv, g, d)
    ps = q_pos.reshape(b, nc, q_chunk)
    # Python-unrolled chunk loop (NOT lax.map): XLA reuses the chunk buffers
    # sequentially so peak memory matches the scan version, while
    # cost_analysis sees every chunk (a scan body is only counted once --
    # the roofline would undercount attention by nc x).
    outs = [chunk_fn((qs[:, i], ps[:, i])) for i in range(nc)]
    out = jnp.concatenate(outs, axis=1)
    return out.astype(q.dtype)


def attention_decode(ctx: ShardCtx, q: jax.Array, ck: jax.Array, cv: jax.Array,
                     valid_len: jax.Array) -> jax.Array:
    """Single-token decode over a (possibly ring) cache.

    q (B,1,H,D); ck/cv (B,W,KV,D); valid_len (B,) number of live slots.
    When ``ctx.seq_shard_cache`` the cache is sequence-sharded over the TP
    axis and attention runs as a shard_map flash-decode with an online-
    softmax cross-shard combine (DESIGN.md §5).
    """
    if ctx.enabled and ctx.seq_shard_cache:
        return _sharded_flash_decode(ctx, q, ck, cv, valid_len)
    from repro.kernels.gqa_decode.ref import gqa_decode_ref
    out = gqa_decode_ref(q[:, 0], ck, cv, valid_len)
    return out[:, None]


def _sharded_flash_decode(ctx: ShardCtx, q, ck, cv, valid_len):
    from repro.launch.meshctx import get_mesh
    mesh = get_mesh()
    _, _, h, d = q.shape
    kv = ck.shape[2]
    g = h // kv
    tp = ctx.tp()
    dp = ctx.dp()

    def local(qx, kx, vx, ln):
        # qx (Bl,1,H,D) replicated over tp; kx/vx (Bl,W/n,KV,D) local shard
        idx = lax.axis_index(tp)
        b = qx.shape[0]                        # LOCAL batch
        wl = kx.shape[1]
        qg = qx[:, 0].reshape(b, kv, g, d).astype(jnp.float32)
        kf = kx.astype(jnp.float32)
        logits = jnp.einsum("bngd,bsnd->bngs", qg, kf) / math.sqrt(d)
        slot = idx * wl + jnp.arange(wl)
        mask = slot[None, :] < ln[:, None]
        logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
        m = jnp.max(logits, axis=-1, keepdims=True)
        m_g = lax.pmax(m, tp)
        p = jnp.exp(logits - m_g)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum("bngs,bsnd->bngd", p, vx.astype(jnp.float32))
        l_g = lax.psum(l, tp)
        acc_g = lax.psum(acc, tp)
        out = acc_g / jnp.maximum(l_g, 1e-30)
        return out.reshape(b, 1, h, d).astype(qx.dtype)

    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, tp, None, None),
                  P(dp, tp, None, None), P(dp)),
        out_specs=P(dp, None, None, None), check_vma=False)
    return f(q, ck, cv, valid_len)


# ---------------------------------------------------------------------------
# QKV projection + cache plumbing for the standard (non-MLA) path
# ---------------------------------------------------------------------------

def qkv_project(p, x, cfg: ModelConfig, positions, mrope=False):
    dt = x.dtype
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    b, s = x.shape[0], x.shape[1]
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = head_rms(p["q_norm"], q, cfg.norm_eps)
        k = head_rms(p["k_norm"], k, cfg.norm_eps)
    secs = cfg.mrope_sections if (mrope or cfg.mrope) else None
    q = apply_rope(q, positions, cfg.rope_theta, secs)
    k = apply_rope(k, positions, cfg.rope_theta, secs)
    return q, k, v


def cache_window(cfg: ModelConfig, max_seq: int) -> int:
    """Ring-buffer length for the KV cache: the SWA window if sub-quadratic,
    else the full sequence."""
    if cfg.attn_kind == "swa":
        return min(max_seq, cfg.window)
    return max_seq


def cache_write(ck, cv, k, v, pos0):
    """Write S new entries at ring positions (pos0 + arange(S)) % W."""
    w = ck.shape[1]
    s = k.shape[1]
    idx = (pos0[:, None] + jnp.arange(s)[None, :]) % w          # (B,S)
    bidx = jnp.arange(ck.shape[0])[:, None]
    ck = ck.at[bidx, idx].set(k)
    cv = cv.at[bidx, idx].set(v)
    return ck, cv


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp(p, x, ctx: ShardCtx):
    dt = x.dtype
    h = jax.nn.silu(x @ p["wg"].astype(dt)) * (x @ p["wi"].astype(dt))
    h = constrain(ctx, h, "dp", None, "tp")
    return h @ p["wo"].astype(dt)
