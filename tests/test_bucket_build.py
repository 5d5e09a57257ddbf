"""The bulk bucket-index build against a plain reference of its contract.

``build_buckets`` / ``bucket_init`` (DESIGN.md §5) must give, bit for bit:
each bucket's ways filled with its live nodes in ascending node id, the
rest EMPTY (key 0); the stash holding the first ``s`` spilled live nodes
by node id, ``stash_n = min(spill, s)``, the overflow latch ``spill > s``;
and ``build_buckets``' spill count.  ``_delta_bucket_patch`` and the
snapshot's stored index rely on that order (DESIGN.md §11).

The structure pin keeps the build a sort and scans: lowered at the
registry's geometry, the recovery program holds no gather and at most one
scatter whose indices span the whole pool.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.hash_probe.ops as hp_ops
from repro.core import SetSpec
from repro.core import shard as SH
from repro.core.nvm import EMPTY, VALID, np_hash32
from repro.core.shard import ShardSpec


def _reference(keys, cur, nb, w, s):
    """The contract, one node at a time in ascending node id."""
    bkeys = np.zeros((nb, w), np.int32)
    bids = np.full((nb, w), EMPTY, np.int32)
    fill = np.zeros(nb, np.int64)
    spilled = []
    for i in np.flatnonzero(cur == VALID):
        b = int(np_hash32(keys[i:i + 1])[0] % nb)
        if fill[b] < w:
            bkeys[b, fill[b]], bids[b, fill[b]] = keys[i], i
            fill[b] += 1
        else:
            spilled.append(i)
    sids = np.full(s, EMPTY, np.int32)
    skeys = np.zeros(s, np.int32)
    first = np.asarray(spilled[:s], np.int64)
    sids[:len(first)] = first
    skeys[:len(first)] = keys[first]
    return (bkeys, bids, skeys, sids, min(len(spilled), s),
            len(spilled) > s, len(spilled))


def _crowded_keys(nb, buckets, count, rng):
    """``count`` distinct keys that all hash into the first ``buckets``
    buckets of an ``nb``-bucket table."""
    cand = rng.choice(1 << 30, 64 * count * nb // buckets, replace=False)
    cand = cand[np_hash32(cand.astype(np.int32)) % nb < buckets]
    assert len(cand) >= count
    return cand[:count].astype(np.int32)


def _pool(case, seed):
    """(keys, cur, nb, w, s) of one named case."""
    rng = np.random.default_rng(seed)
    n, nb, w, s = 1024, 256, 8, 32
    keys = rng.choice(1 << 30, n, replace=False).astype(np.int32)
    stages = rng.integers(0, 5, n).astype(np.int32)   # VALID ~ 1 in 5
    if case == "half_live":
        cur = np.where(rng.random(n) < 0.5, VALID, stages)
    elif case == "empty":
        cur = np.where(stages == VALID, 0, stages)
    elif case == "full":
        cur = np.full(n, VALID, np.int32)
    elif case == "crowded":            # a few buckets overflow, spill < s
        keys[:40] = _crowded_keys(nb, 4, 40, rng)
        cur = np.where(np.arange(n) < 40, VALID, stages)
    elif case == "spill_over_stash":   # spill > s: the latch
        keys[:200] = _crowded_keys(nb, 8, 200, rng)
        cur = np.where(rng.random(n) < 0.9, VALID, stages)
    elif case == "non_pow2":
        n, nb, w, s = 1000, 96, 4, 24
        keys, cur = keys[:n], np.where(rng.random(n) < 0.8, VALID,
                                       stages[:n])
    else:
        raise ValueError(case)
    # a dead slot keeps whatever key it held: the build must ignore it
    return keys, cur.astype(np.int32), nb, w, s


CASES = ["half_live", "empty", "full", "crowded", "spill_over_stash",
         "non_pow2"]


def _assert_same(got, want):
    names = ("bkeys", "bids", "skeys", "sids", "stash_n", "overflow")
    for name, g, x in zip(names, got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x),
                                      err_msg=name)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("case", CASES)
def test_bucket_init_matches_reference(case, seed):
    keys, cur, nb, w, s = _pool(case, seed)
    want = _reference(keys, cur, nb, w, s)
    got = hp_ops.bucket_init(jnp.asarray(keys), jnp.asarray(cur),
                             nb=nb, w=w, s=s)
    _assert_same(got, want)
    bkeys, bids, spill = hp_ops.build_buckets(jnp.asarray(keys),
                                              jnp.asarray(cur), nb=nb, w=w)
    _assert_same((bkeys, bids), want[:2])
    assert int(spill) == want[-1]
    if case == "spill_over_stash":
        assert want[-1] > s, "the case must spill past the stash"
    if case == "crowded":
        assert 0 < want[-1] < s, "the case must spill into the stash"


def test_bucket_init_vmapped_over_shards():
    """As ``shard.recover`` runs it: one vmapped build over the shard axis,
    each shard equal to its own reference."""
    pools = [_pool(case, 7) for case in
             ("half_live", "empty", "full", "crowded", "spill_over_stash")]
    nb, w, s = pools[0][2:]
    keys = np.stack([p[0] for p in pools])
    cur = np.stack([p[1] for p in pools])
    got = jax.vmap(functools.partial(hp_ops.bucket_init, nb=nb, w=w, s=s))(
        jnp.asarray(keys), jnp.asarray(cur))
    for i in range(len(pools)):
        _assert_same([g[i] for g in got],
                     _reference(keys[i], cur[i], nb, w, s))


def _whole_pool_indexing(lowered, pool):
    """(op name, index tuples) of every gather and scatter in a lowered
    program that indexes at least ``pool`` elements."""
    found = []

    def walk(op):
        name = op.operation.name
        if name in ("stablehlo.gather", "stablehlo.scatter"):
            shape = op.operation.operands[1].type.shape
            tuples = int(np.prod(shape[:-1]))
            if tuples >= pool:
                found.append((name, tuples))
        for region in op.operation.regions:
            for block in region.blocks:
                for inner in block.operations:
                    walk(inner)

    walk(lowered.compiler_ir("stablehlo").operation)
    return found


def test_recover_program_holds_no_whole_pool_gather():
    """``shard.recover`` at the registry's geometry (8 shards of 2^17
    slots, bucket backend): no whole-pool gather, at most one whole-pool
    scatter.  The rebuild is one sort that carries its operands."""
    pool = 1 << 17
    sspec = ShardSpec(base=SetSpec(capacity=8 * pool, backend="bucket"),
                      n_shards=8)
    plane = jax.ShapeDtypeStruct((8, pool), jnp.int32)
    found = _whole_pool_indexing(
        SH.recover.lower(plane, plane, plane, plane, sspec=sspec), pool)
    assert not [f for f in found if f[0] == "stablehlo.gather"], found
    assert len(found) <= 1, found
