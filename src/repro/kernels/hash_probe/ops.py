"""Jit'd wrappers tying the probe kernel to the durable-set state.

Two regimes (DESIGN.md §5):

  bulk         ``build_buckets`` / ``bucket_init`` pack the whole node pool
               into the (NB, W) table -- an O(N log N) argsort repack paid
               ONLY at state construction and recovery.
  incremental  ``bucket_insert`` / ``bucket_remove`` maintain the same table
               with O(B*W) per-lane scatter writes -- the hot path.  A lane
               claims the first free way of its bucket, spills to the dense
               stash on per-bucket overflow, and frees the way (or stash
               slot) on delete.

``lookup`` is then a pure read of the carried table through the Pallas MXU
kernel ``probe_pallas`` (or the jnp reference).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.nvm import hash32, EMPTY, VALID
from repro.kernels.hash_probe.kernel import probe_pallas
from repro.kernels.hash_probe.ref import probe_ref


@functools.partial(jax.jit, static_argnames=("nb", "w"))
def build_buckets(keys: jax.Array, cur: jax.Array, nb: int = 1024, w: int = 8):
    """Pack live nodes of a durable-set pool into a (NB, W) bucket table.

    Deterministic way assignment: rank of each node among same-bucket live
    nodes (computed with a sort), overflowing entries dropped into the dense
    stash handled by the wrapper (rare under load factor <= 0.5)."""
    n = keys.shape[0]
    assert n < (1 << 24), "pool size exceeds the f32-exact node-id budget"
    live = cur == VALID
    bucket = (hash32(keys) % jnp.uint32(nb)).astype(jnp.int32)
    bucket = jnp.where(live, bucket, nb)          # dead nodes -> overflow bin
    order = jnp.argsort(bucket)                   # stable: groups same bucket
    sorted_b = bucket[order]
    # rank within bucket group
    idx = jnp.arange(n, dtype=jnp.int32)
    group_start = jnp.full((nb + 1,), n, jnp.int32).at[sorted_b].min(
        idx, mode="drop")
    rank = idx - group_start[jnp.clip(sorted_b, 0, nb)]
    ok = (sorted_b < nb) & (rank < w)
    flat = jnp.where(ok, sorted_b * w + rank, nb * w)
    bkeys = jnp.zeros((nb * w,), jnp.int32).at[flat].set(
        keys[order], mode="drop").reshape(nb, w)
    bids = jnp.full((nb * w,), -1, jnp.int32).at[flat].set(
        order.astype(jnp.int32), mode="drop").reshape(nb, w)
    overflow = jnp.sum((sorted_b < nb) & (rank >= w))
    return bkeys, bids, overflow


@functools.partial(jax.jit, static_argnames=("nb", "w", "s"))
def bucket_init(keys: jax.Array, cur: jax.Array, *, nb: int, w: int, s: int):
    """Bulk build of the full incremental index: (NB, W) bucket table plus
    the dense stash holding the live nodes that overflowed their bucket.
    Returns (bkeys, bids, skeys, sids, stash_n, overflow) -- overflow is
    True when more than ``s`` nodes spilled (data would be unreachable)."""
    bkeys, bids, _ = build_buckets(keys, cur, nb=nb, w=w)
    n = keys.shape[0]
    flat = bids.reshape(-1)
    in_table = jnp.zeros((n,), jnp.bool_).at[
        jnp.where(flat >= 0, flat, n)].set(True, mode="drop")
    stashed = (cur == VALID) & ~in_table
    spill = jnp.sum(stashed.astype(jnp.int32))
    idx = jnp.where(stashed, size=s, fill_value=-1)[0].astype(jnp.int32)
    got = idx >= 0
    sids = jnp.where(got, idx, EMPTY)
    skeys = jnp.where(got, keys[jnp.clip(idx, 0)], 0)
    return bkeys, bids, skeys, sids, jnp.minimum(spill, s), spill > s


def _nth_free(free: jax.Array, rank: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per row of ``free`` (B, K): the column of the (rank+1)-th free slot
    in ascending order, plus a found flag.  This is exactly the slot a lane
    of claim-order ``rank`` receives from sequential first-free claiming,
    because slots are only ever *consumed* within one call."""
    c = jnp.cumsum(free.astype(jnp.int32), axis=1)
    hit = free & (c == (rank + 1)[:, None])
    ok = hit.any(axis=1)
    col = jnp.argmax(hit, axis=1).astype(jnp.int32)
    return col, ok


def bucket_insert(bkeys, bids, skeys, sids, stash_n, keys, ids, do):
    """Incremental insert: for lanes with do[i], place node ids[i] (key
    keys[i]) into the first free way of its bucket, or the first free dense
    stash slot when the bucket is full.

    Vectorized sequential-equivalent: lane order is the linearization order
    (exactly as in ``_table_write_ref``), and since ways/slots are only
    consumed
    here, the lane of in-bucket claim-rank r deterministically receives the
    (r+1)-th free way -- one O(B^2) rank computation plus ONE scatter per
    plane instead of a B-step sequential loop (the former apply_batch
    bottleneck)."""
    nb, _ = bkeys.shape
    b = keys.shape[0]
    bucket = (hash32(keys) % jnp.uint32(nb)).astype(jnp.int32)
    earlier = jnp.tril(jnp.ones((b, b), jnp.bool_), k=-1)

    # claim order among do-lanes of the same bucket == sequential lane order
    same = do[:, None] & do[None, :] & (bucket[:, None] == bucket[None, :])
    rank = jnp.sum(same & earlier, axis=1).astype(jnp.int32)
    way, has_way = _nth_free(bids[bucket] == EMPTY, rank)
    place = do & has_way
    tb = jnp.where(place, bucket, nb)                  # OOB scatter => drop
    bkeys = bkeys.at[tb, way].set(keys, mode="drop")
    bids = bids.at[tb, way].set(ids, mode="drop")

    # bucket-full lanes spill to the dense stash, same claim-rank argument
    spill = do & ~has_way
    srank = jnp.sum(spill[:, None] & spill[None, :] & earlier,
                    axis=1).astype(jnp.int32)
    slot, has_slot = _nth_free((sids == EMPTY)[None, :].repeat(b, 0), srank)
    put = spill & has_slot
    ts = jnp.where(put, slot, sids.shape[0])
    skeys = skeys.at[ts].set(keys, mode="drop")
    sids = sids.at[ts].set(ids, mode="drop")
    stash_n = stash_n + jnp.sum(put.astype(jnp.int32))
    ovf = (spill & ~has_slot).any()
    return bkeys, bids, skeys, sids, stash_n, ovf


def bucket_remove(bkeys, bids, skeys, sids, stash_n, keys, ids, do):
    """Incremental delete: free the way (or dense stash slot) holding node
    ids[i] for lanes with do[i].  A live node is in the bucket table XOR
    the stash, so exactly one of the two clears fires.  Do-lanes carry
    DISTINCT node ids (the op bodies dedup by lane priority), so all
    scatter targets are distinct and one scatter per plane suffices."""
    nb, _ = bkeys.shape
    bucket = (hash32(keys) % jnp.uint32(nb)).astype(jnp.int32)

    hitw = bids[bucket] == ids[:, None]                # (B, W)
    in_table = do & hitw.any(axis=1)
    way = jnp.argmax(hitw, axis=1).astype(jnp.int32)
    tb = jnp.where(in_table, bucket, nb)               # OOB scatter => drop
    bids = bids.at[tb, way].set(EMPTY, mode="drop")
    bkeys = bkeys.at[tb, way].set(0, mode="drop")

    hits = sids[None, :] == ids[:, None]               # (B, S)
    in_stash = do & ~in_table & hits.any(axis=1)
    slot = jnp.argmax(hits, axis=1).astype(jnp.int32)
    ts = jnp.where(in_stash, slot, sids.shape[0])
    sids = sids.at[ts].set(EMPTY, mode="drop")
    skeys = skeys.at[ts].set(0, mode="drop")
    stash_n = stash_n - jnp.sum(in_stash.astype(jnp.int32))
    return bkeys, bids, skeys, sids, stash_n, jnp.bool_(False)


@functools.partial(jax.jit, static_argnames=("max_probe", "interpret"))
def table_lookup(table: jax.Array, pool_keys: jax.Array, q_keys: jax.Array,
                 *, max_probe: int = 128, interpret: Optional[bool] = None
                 ) -> jax.Array:
    """Linear-probe-table lookup routed through the tiled ``probe_pallas``
    MXU kernel (the probe backend's read path, DESIGN.md §2a).

    Each lane's probe window is gathered ONCE into (B, P) key/id planes and
    becomes its own bucket row (q_bucket == lane index), so the probe
    backend shares the one-hot-matmul kernel the bucket backend uses.  The
    linear-probing insert invariant (an entry is always placed at or before
    the first EMPTY of its chain, and EMPTY slots are never created by
    operation -- deletes write TOMB) makes the kernel's any-match join equal
    to the sequential first-match-before-EMPTY result.  Any batch size (the
    kernel pads to whole query tiles); node ids must stay within the
    kernel's 2^24 budget.  ``interpret`` defaults to the platform."""
    t = table.shape[0]
    b = q_keys.shape[0]
    n = pool_keys.shape[0]
    assert n < (1 << 24), "pool size exceeds the kernel's node-id budget"
    h = (hash32(q_keys) & jnp.uint32(t - 1)).astype(jnp.int32)
    pos = (h[:, None]
           + jnp.arange(max_probe, dtype=jnp.int32)[None, :]) & (t - 1)
    ids = table[pos]                                       # (B, P) id plane
    live = ids >= 0
    wkeys = jnp.where(live, pool_keys[jnp.clip(ids, 0, n - 1)], 0)
    wids = jnp.where(live, ids, EMPTY)                     # mask TOMB too
    rows = jnp.arange(b, dtype=jnp.int32)                  # lane i -> row i
    return probe_pallas(wkeys, wids, rows, q_keys, interpret=interpret)


def lookup(bucket_keys, bucket_ids, q_keys, *, use_pallas=True):
    """Bucket-table lookup: the ``probe_pallas`` kernel, or the jnp
    reference when ``use_pallas`` is False."""
    nb = bucket_keys.shape[0]
    qb = (hash32(q_keys) % jnp.uint32(nb)).astype(jnp.int32)
    if use_pallas:
        return probe_pallas(bucket_keys, bucket_ids, qb, q_keys)
    return probe_ref(bucket_keys, bucket_ids, qb, q_keys)
