"""Pallas TPU kernel: recovery validity scan over the durable areas.

After a crash the recovery procedure must classify every node in every
durable area (Sections 3.5 / 4.6; DESIGN.md §2) -- reachable from the
public API through the "bucket" index backend (DESIGN.md §4).  On TPU this is a bandwidth-bound
streaming pass; the kernel tiles the stage vector through VMEM, emits the
member mask, and accumulates a per-stage histogram (the recovery telemetry:
how many nodes were torn / deleted / live) in a VMEM accumulator that is
written once at the last grid step.

Tiling: the stage vector is padded with -1 (no stage: neither member nor
counted) and laid out as (rows, 128) lanes; grid (rows / TR), stage tile
i32[TR, 128] -> mask tile + per-lane histogram rows (8, 128), summed over
lanes by the wrapper.  Every block is 2-D with (8k, 128) trailing dims, so
the kernel also lowers under ``jax.vmap`` over a stacked shard axis (the
batch dim lands in front of the block).  TR = 512 rows keeps the tile at
256 KiB and the pass fully pipelined on HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

N_STAGES = 5
LANES = 128
_HIST_ROWS = 8           # N_STAGES padded to one sublane tile


def _scan_kernel(stage_ref, mask_ref, hist_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    stage = stage_ref[...]
    mask_ref[...] = (stage == 3).astype(jnp.int32)
    for b in range(N_STAGES):
        hist_ref[b:b + 1, :] += jnp.sum((stage == b).astype(jnp.int32),
                                        axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("nt", "interpret"))
def scan_pallas(persisted: jax.Array, *, nt: int = 65536,
                interpret: Optional[bool] = None):
    """persisted i32[N] -> (member mask bool[N], stage histogram i32[5]).

    ``nt`` is the tile in stages (rounded to whole 8x128 tiles); any N.
    ``interpret`` defaults to the platform."""
    interpret = resolve_interpret(interpret)
    n = persisted.shape[0]
    rows = pl.cdiv(n, LANES)
    tr = max(8, (nt // LANES) // 8 * 8)
    if tr >= rows:
        tr = rows                                 # one block: the whole array
    rows_p = pl.cdiv(rows, tr) * tr
    x = jnp.pad(persisted, (0, rows_p * LANES - n),
                constant_values=-1).reshape(rows_p, LANES)
    mask, hist = pl.pallas_call(
        _scan_kernel,
        grid=(rows_p // tr,),
        in_specs=[pl.BlockSpec((tr, LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((_HIST_ROWS, LANES), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows_p, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((_HIST_ROWS, LANES), jnp.int32)],
        interpret=interpret,
    )(x)
    return (mask.reshape(-1)[:n].astype(jnp.bool_),
            hist[:N_STAGES].sum(axis=1))
