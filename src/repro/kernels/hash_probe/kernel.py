"""Pallas TPU kernel: bucketized hash-table probe via MXU one-hot gather.

TPU adaptation of the paper's hash-bucket traversal (DESIGN.md §2) -- the
lookup path of the "bucket" index backend (DESIGN.md §4): pointer
chasing does not map to a systolic machine, so the volatile index becomes a
set-associative table (NB buckets x W ways) and the random bucket *gather*
is performed on the MXU as a one-hot matmul.

Exactness: the table is carried into the kernel as byte planes -- the four
bytes of each key and the three low bytes of each ``id + 1`` -- stacked
into one lane-dense bf16 matrix of shape (7W, NB).  Every byte is exact in
bf16, the one-hot has at most one 1 per column, and the MXU accumulates in
f32, so the gathered bytes are exact whatever the matmul precision.  Node
ids therefore stay below 2^24 (the wrapper's and ``SetSpec``'s budget);
``id + 1 == 0`` marks an empty way.

Tiling: grid (B / BQ, NB / NBT), queries and results as (1, B) rows so
every block is lane-dense.  Each program builds the transposed one-hot
(NBT, BQ) for its query tile against its bucket tile, gathers
``planes (7W, NBT) @ onehot (NBT, BQ) -> (7W, BQ)``, reassembles keys and
ids per way, and folds the match into the output with a running max over
bucket tiles (ids are unique, empty == -1, so max is the join).  VMEM per
program at BQ=128, NBT=4096, W=8: the plane block 7*8*4096*2 B = 448 KiB
(double-buffered), the one-hot with its iota and f32 staging ~5 MiB, the
(56, 128) gather and the query/output rows a few KiB -- inside the v5e's
16 MiB default scoped VMEM.  Wider tables take a narrower NBT (the plane
block is held near 448 KiB).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

BQ = 128                 # query lanes per program (one full lane row)
_PLANE_ELEMS = 1 << 15   # W * NBT: bounds the plane block per program
_KEY_BYTES, _ID_BYTES = 4, 3


def _probe_kernel(qb_ref, qk_ref, planes_ref, out_ref, *, nbt: int, w: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, -1)

    local = qb_ref[...] - j * nbt                       # (1, BQ)
    rows = jax.lax.broadcasted_iota(jnp.int32, (nbt, BQ), 0)
    onehot = jnp.where(rows == local, 1.0, 0.0).astype(jnp.bfloat16)
    g = jnp.dot(planes_ref[...], onehot,
                preferred_element_type=jnp.float32).astype(jnp.int32)

    def word(first: int, n_bytes: int):
        out = g[first * w:(first + 1) * w]
        for b in range(1, n_bytes):
            out = out | (g[(first + b) * w:(first + b + 1) * w] << (8 * b))
        return out                                      # (W, BQ)

    key = word(0, _KEY_BYTES)
    id1 = word(_KEY_BYTES, _ID_BYTES)                   # id + 1; 0 == empty
    match = (key == qk_ref[...]) & (id1 > 0)
    found = jnp.max(jnp.where(match, id1 - 1, -1), axis=0, keepdims=True)
    out_ref[...] = jnp.maximum(out_ref[...], found)


def _byte_planes(bucket_keys: jax.Array, bucket_ids: jax.Array) -> jax.Array:
    """(NB, W) key/id tables -> the (7W, NB) bf16 byte-plane matrix."""
    k = bucket_keys.view(jnp.uint32)
    i = (bucket_ids + 1).view(jnp.uint32)
    parts = ([(k >> (8 * b)) & 0xFF for b in range(_KEY_BYTES)]
             + [(i >> (8 * b)) & 0xFF for b in range(_ID_BYTES)])
    return jnp.concatenate([p.T for p in parts], axis=0).astype(jnp.bfloat16)


def _bucket_tile(nb: int, w: int) -> int:
    """Buckets per program: all of them for small tables, else the
    power-of-two tile (a multiple of 128 lanes) holding W * NBT near
    ``_PLANE_ELEMS``."""
    cap = max(128, _PLANE_ELEMS // w)
    cap = 1 << (cap.bit_length() - 1)
    return nb if nb <= cap else cap


@functools.partial(jax.jit, static_argnames=("interpret",))
def probe_pallas(bucket_keys: jax.Array, bucket_ids: jax.Array,
                 q_bucket: jax.Array, q_keys: jax.Array,
                 *, interpret: Optional[bool] = None) -> jax.Array:
    """Bucketized lookup: node id per query, or -1.

    Shapes: bucket_keys/bucket_ids i32[NB, W] (ids < 2^24, -1 == empty
    way), q_bucket/q_keys i32[B].  Any NB and B: queries are padded to
    whole 128-lane tiles with a bucket index no tile holds, and the table
    to whole bucket tiles with empty ways.  ``interpret`` defaults to the
    platform (compiled on TPU, interpreted elsewhere)."""
    interpret = resolve_interpret(interpret)
    nb, w = bucket_keys.shape
    b = q_keys.shape[0]
    nbt = _bucket_tile(nb, w)
    nb_p = pl.cdiv(nb, nbt) * nbt
    b_p = pl.cdiv(b, BQ) * BQ

    planes = _byte_planes(bucket_keys, bucket_ids)
    planes = jnp.pad(planes, ((0, 0), (0, nb_p - nb)))
    qb = jnp.pad(q_bucket, (0, b_p - b), constant_values=-1)[None, :]
    qk = jnp.pad(q_keys, (0, b_p - b))[None, :]
    rows = planes.shape[0]

    out = pl.pallas_call(
        functools.partial(_probe_kernel, nbt=nbt, w=w),
        grid=(b_p // BQ, nb_p // nbt),
        in_specs=[
            pl.BlockSpec((1, BQ), lambda i, j: (0, i)),       # q bucket
            pl.BlockSpec((1, BQ), lambda i, j: (0, i)),       # q key
            pl.BlockSpec((rows, nbt), lambda i, j: (0, j)),   # byte planes
        ],
        out_specs=pl.BlockSpec((1, BQ), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, b_p), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qb, qk, planes)
    return out[0, :b]
