"""Jit'd wrappers tying the probe kernel to the durable-set state.

Two regimes (DESIGN.md §5):

  bulk         ``build_buckets`` / ``bucket_init`` pack the whole node pool
               into the (NB, W) table and the stash -- two sorts that carry
               their operands and a scan, no gather or scatter -- paid ONLY
               on a full rebuild at recovery (the index is born empty).
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
from jax import lax

from repro.core.nvm import hash32, EMPTY, VALID
from repro.kernels.hash_probe.kernel import probe_pallas
from repro.kernels.hash_probe.ref import probe_ref


@functools.partial(jax.jit, static_argnames=("nb", "w", "s"))
def build_buckets(keys: jax.Array, cur: jax.Array, nb: int = 1024, w: int = 8,
                  s: Optional[int] = None):
    """Pack the live nodes of a durable-set pool into a (NB, W) table.

    Each bucket's ways hold its live nodes in ascending node id, the rest
    EMPTY (key 0); the live nodes past a bucket's ``w`` ways spill.
    Returns (bkeys, bids, spill count); given ``s``, also the first ``s``
    spilled nodes by node id, (skeys, sids), EMPTY-padded.

    No gather and no scatter: every bucket gets ``w`` dummy entries with
    ids above every node, so that one sort by (bucket, id), carrying the
    ids and keys, leaves each bucket's group at least ``w`` long with its
    live nodes first.  A scan ranks each entry within its group; a second
    sort moves the ``w`` first of every group to the front -- the table's
    rows, in order -- followed by the spilled nodes in id order."""
    n = keys.shape[0]
    m = nb * w
    assert n < (1 << 24), "pool size exceeds the f32-exact node-id budget"
    assert 3 * (n + m) < (1 << 31), "bucket table exceeds the i32 sort key"
    bucket = (hash32(keys) % jnp.uint32(nb)).astype(jnp.int32)
    bucket = jnp.where(cur == VALID, bucket, nb)       # dead nodes sort last
    ids = jnp.arange(n, dtype=jnp.int32)
    sb, sid, sk = lax.sort(
        (jnp.concatenate([bucket, jnp.repeat(jnp.arange(nb, dtype=jnp.int32),
                                             w)]),
         jnp.concatenate([ids, n + jnp.tile(jnp.arange(w, dtype=jnp.int32),
                                            nb)]),
         jnp.concatenate([keys, jnp.zeros((m,), keys.dtype)])),
        num_keys=2, is_stable=False)
    pos = jnp.arange(n + m, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), sb[1:] != sb[:-1]])
    rank = pos - lax.cummax(jnp.where(first, pos, 0))
    kept = (sb < nb) & (rank < w)
    spilled = (sb < nb) & (rank >= w) & (sid < n)
    # the kept entries in table order, then the spilled nodes by id, then
    # the dead nodes and the unused dummies
    _, tid, tk = lax.sort(
        (jnp.where(kept, pos, jnp.where(spilled, n + m + sid,
                                        2 * (n + m) + pos)), sid, sk),
        num_keys=1, is_stable=False)
    node = tid[:m] < n
    bkeys = jnp.where(node, tk[:m], 0).reshape(nb, w)
    bids = jnp.where(node, tid[:m], EMPTY).reshape(nb, w)
    spill = jnp.sum(spilled.astype(jnp.int32))
    if s is None:
        return bkeys, bids, spill
    k = min(s, n)                          # at most n nodes can spill
    got = jnp.arange(k) < spill
    skeys = jnp.pad(jnp.where(got, tk[m:m + k], 0), (0, s - k))
    sids = jnp.pad(jnp.where(got, tid[m:m + k], EMPTY), (0, s - k),
                   constant_values=EMPTY)
    return bkeys, bids, spill, skeys, sids


@functools.partial(jax.jit, static_argnames=("nb", "w", "s"))
def bucket_init(keys: jax.Array, cur: jax.Array, *, nb: int, w: int, s: int):
    """Bulk build of the full incremental index: (NB, W) bucket table plus
    the dense stash holding the first ``s`` live nodes, by node id, that
    overflowed their bucket (``build_buckets``).
    Returns (bkeys, bids, skeys, sids, stash_n, overflow) -- overflow is
    True when more than ``s`` nodes spilled (data would be unreachable)."""
    bkeys, bids, spill, skeys, sids = build_buckets(keys, cur, nb=nb, w=w,
                                                    s=s)
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
