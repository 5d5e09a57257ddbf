"""GPipe pipeline parallelism over a ``pipe`` mesh axis via shard_map +
collective_permute (DESIGN.md §5).

Stages hold disjoint layer slices; microbatches stream through with the
classic (M + S - 1)-step schedule; activations move stage-to-stage with
ppermute.  The schedule loop is python-unrolled so the dry-run cost
analysis sees every step.

This is an optional composition layer: ``gpipe_fn`` wraps any
shape-preserving stage function (params_i, x) -> x.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def gpipe_fn(stage_fn: Callable[[Any, jax.Array], jax.Array],
             mesh: jax.sharding.Mesh, axis: str = "pipe"):
    """Build a pipelined apply: (stage_params, x_micro) -> y_micro.

    stage_params: pytree with leading dim == n_stages (stage slice each).
    x_micro: (M, mb, ...) microbatches; returns same shape after all stages.
    """
    s = mesh.shape[axis]

    def local(params_local, xm):
        # params_local: stage slice with leading dim 1; xm: full (M, mb, ...)
        idx = lax.axis_index(axis)
        m = xm.shape[0]
        p_i = jax.tree.map(lambda a: a[0], params_local)
        buf = jnp.zeros_like(xm[0])
        outs = jnp.zeros_like(xm)
        perm = [(i, (i + 1) % s) for i in range(s)]

        for t in range(m + s - 1):
            # stage 0 ingests microbatch t during warmup+steady
            feed = xm[min(t, m - 1)]
            cur = jnp.where((idx == 0) & (t < m), feed, buf)
            active = (t - idx >= 0) & (t - idx < m)
            y = stage_fn(p_i, cur)
            y = jnp.where(active, y, cur)
            # last stage emits microbatch t - s + 1
            oi = t - (s - 1)
            if oi >= 0:
                emit = (idx == s - 1) & active
                outs = outs.at[oi].set(jnp.where(emit, y, outs[oi]))
            buf = lax.ppermute(y, axis, perm)
        # results live on the last stage; share them with everyone
        outs = lax.psum(jnp.where(idx == s - 1, outs, jnp.zeros_like(outs)),
                        axis)
        return outs

    def run(stage_params, x_micro):
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis), P(*(None,) * x_micro.ndim)),
            out_specs=P(*(None,) * x_micro.ndim),
            check_vma=False)(stage_params, x_micro)

    return run
