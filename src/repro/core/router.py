"""Router v2: device-local two-stage routing with adaptive lane budgets.

The PR-3 single-stage router (``shard.route``) computes the full (S, L)
lane grid *globally* and hands it to the vmapped dispatch; under
``shard_map`` that implies every device materializes the whole batch (an
all-gather of B lanes) before slicing out its own shards, and the static
``lane_factor=2`` budget caps every quadratic term's shard shrink at 2x.
Router v2 removes both:

  stage 1 (host)   runs OUTSIDE jit in numpy: the mixed batch is split
                   into D per-device sub-batches by the top ``log2(D)``
                   bits of the shard id (itself the top ``log2(S)`` bits
                   of ``hash32``), so each device's program only ever
                   receives its own lanes -- no cross-device collective
                   exists in the compiled program (pinned by
                   ``tests/test_router_v2.py``).  The same pass measures
                   the realized per-shard occupancy histogram for free.
  stage 2 (in-jit) the PR-3 sort/segment router, now *per device* over
                   the device's ``S/D`` local shards, with an ADAPTIVE
                   lane budget: L = the smallest power of two covering
                   the realized max shard occupancy (clamped to
                   ``[min_lane_budget, max_lane_budget or B]``), chosen
                   from the same bucketed-retrace family as the existing
                   pow2 batch rounding.  Healthy batches get
                   L = next_pow2(max occupancy) ~ B/S instead of the
                   flat ``2*B/S``, and a skewed batch widens L instead
                   of dropping lanes; drops now happen ONLY when the
                   operator caps the budget (``max_lane_budget``).

Placement (``ShardSpec.placement``) decides which global shards a device
owns when S >> D -- "contiguous" (device d owns shard block
[d*S/D, (d+1)*S/D), the PR-3 layout: storage row == global shard id) or
"strided" (device d owns {d, d+D, d+2D, ...}).  Placement only permutes
the storage order of the stacked state's leading axis; per-shard
semantics, psync accounting, and recovery are row-local and unaffected.

Conformance: on any drop-free trace (every within-budget workload), for
any D, any placement, and any adaptive budget, Router v2 executes
exactly the same lanes in exactly the same per-shard order as the v1
router (stage 1 preserves lane order inside each device; stage 2's
stable sort preserves it inside each shard; same-key lanes always share
a shard), so results, state, and psync counters are bit-identical -- the
conformance suite in ``tests/test_router_v2.py`` pins this across all
three index backends.  Under budget pressure the drop sets differ by
design: v1's static budget sheds skew that uncapped v2 widens L to
absorb.

Pipelining (DESIGN.md §6): every routing artifact this module produces is
VOLATILE -- NVTraverse's traverse-volatile/persist-destination rule means
the lane grids, slot maps, and occupancy histograms carry no durability
obligation, so stage 1 of batch k+1 may run on the host WHILE the jitted
stage-2 program of batch k executes on device (JAX async dispatch), and
the gather-back may be deferred until a caller actually reads the
results.  :func:`apply_batch_v2_async` / :func:`get_v2_async` return an
:class:`InFlight` whose ``force()`` performs the only host sync;
the synchronous entrypoints are the same machinery forced immediately,
so results, state, and psync counters are bit-identical by construction
(pinned by ``tests/test_pipeline.py``).  Host scratch (the (D, Bd) lane
grids and the slot map) comes from a per-geometry pool and is recycled
once its batch has been forced -- steady-state routing allocates nothing.

This module must not import :mod:`repro.core.shard` (shard.py imports
it); ``sspec`` arguments are duck-typed ``ShardSpec`` instances.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, TYPE_CHECKING, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core import engine as E
from repro.core.engine import OP_CONTAINS, OP_NOP
from repro.core.nvm import hash32, np_hash32
from repro.obs.metrics import span

if TYPE_CHECKING:                                   # pragma: no cover
    from repro.core.shard import ShardSpec

PLACEMENTS = ("contiguous", "strided")


# ---------------------------------------------------------------------------
# Placement: global shard id <-> storage row of the stacked state's dim0.
# shard_map always hands device d the CONTIGUOUS dim0 block
# [d*S/D, (d+1)*S/D), so a placement policy is a permutation of storage
# rows: "contiguous" is the identity (PR-3 layout), "strided" interleaves.
# ---------------------------------------------------------------------------


def mesh_devices(sspec) -> int:
    """Devices the shard axis can split over: the largest power-of-two
    divisor of n_shards that the process has devices for (1 == plain
    vmap)."""
    if not sspec.use_shard_map:
        return 1
    d = sspec.n_shards
    avail = jax.device_count()
    while d > 1 and d > avail:
        d //= 2
    return d


def resolve_groups(sspec) -> int:
    """Stage-1 group count D: an explicit ``n_device_groups`` override, or
    the mesh size (1 unless ``use_shard_map`` on a multi-device process).
    Always a power of two dividing ``n_shards``."""
    g = sspec.n_device_groups or mesh_devices(sspec)
    return min(g, sspec.n_shards)


def np_storage_rows(sspec, n_groups: int) -> np.ndarray:
    """Storage row per GLOBAL shard id, i32[S] (identity for contiguous)."""
    s = sspec.n_shards
    sid = np.arange(s, dtype=np.int32)
    if sspec.placement == "contiguous" or n_groups <= 1:
        return sid
    per = s // n_groups
    return (sid % n_groups) * per + sid // n_groups


def _np_row_of(keys: np.ndarray, sspec, n_groups: int) -> np.ndarray:
    """Storage row per key (host twin of the in-jit stage-2 math)."""
    s = sspec.n_shards
    if s == 1:
        return np.zeros(keys.shape, np.int32)
    sbits = s.bit_length() - 1
    sid = (np_hash32(keys) >> np.uint32(32 - sbits)).astype(np.int32)
    if sspec.placement == "contiguous" or n_groups <= 1:
        return sid
    per = s // n_groups
    return (sid % n_groups) * per + sid // n_groups


def _pow2_at_least(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def adaptive_lane_budget(sspec, batch: int, max_occ: int) -> int:
    """Stage-2 lane budget: the smallest power of two >= the REALIZED max
    per-shard occupancy, clamped to [min_lane_budget, max_lane_budget or
    B].  Power-of-two choice keeps the retrace set small (log2(B)
    variants); the ``max_lane_budget`` cap is the only source of drops."""
    if sspec.n_shards == 1:
        return max(int(batch), 1)
    lane = max(_pow2_at_least(max_occ), min(sspec.min_lane_budget, batch))
    if sspec.max_lane_budget:
        lane = min(lane, sspec.max_lane_budget)
    return max(1, min(lane, batch))


def budget_candidates(sspec, batch: int) -> Tuple[int, ...]:
    """The pre-compilable budget set for a B-lane batch: every value
    :func:`adaptive_lane_budget` can return.  Enumerated by sweeping the
    pow2 occupancy steps (L only changes at next_pow2(max_occ)
    boundaries), so non-pow2 clamps are handled exactly."""
    batch = max(int(batch), 1)
    if sspec.n_shards == 1:
        return (batch,)
    return tuple(sorted({adaptive_lane_budget(sspec, batch, 1 << i)
                         for i in range(batch.bit_length() + 1)}))


# ---------------------------------------------------------------------------
# Host routing scratch: pooled per-(D, Bd, B) numpy buffers.
#
# Stage 1 used to allocate fresh (D, Bd) grids + a slot map every batch;
# at the canonical 1024-lane geometry that is ~3 MB of allocator traffic
# per round and, under the pipelined dispatch path, garbage churn racing
# the device.  The pool recycles a scratch set once the batch that used
# it has been FORCED (its device execution is complete, so even a
# zero-copy host->device transfer no longer aliases the buffers).  A
# ``RoutePlan``'s numpy views are therefore valid until its batch is
# forced AND a later batch at the same geometry acquires the recycled
# set -- treat plan telemetry as transient.
# ---------------------------------------------------------------------------


class _Scratch:
    """One reusable stage-1 buffer set for a (D, Bd, B) geometry."""
    __slots__ = ("key", "d_ops", "d_keys", "d_vals", "slot")

    def __init__(self, key):
        d, bd, b = key
        self.key = key
        self.d_ops = np.empty((d, bd), np.int32)
        self.d_keys = np.empty((d, bd), np.int32)
        self.d_vals = np.empty((d, bd), np.int32)
        self.slot = np.empty((b,), np.int64)


class _ScratchPool:
    """Free-list of :class:`_Scratch` sets keyed by geometry.

    ``grid_allocs`` counts real buffer allocations; at a steady-state
    geometry it must stay flat (the allocation-count regression test in
    ``tests/test_pipeline.py`` pins this).
    """

    def __init__(self):
        self._free = {}
        self.grid_allocs = 0
        self.acquires = 0
        self.releases = 0

    def acquire(self, d: int, bd: int, b: int) -> _Scratch:
        key = (d, bd, b)
        self.acquires += 1
        free = self._free.get(key)
        if free:
            return free.pop()
        self.grid_allocs += 1
        return _Scratch(key)

    def release(self, scratch) -> None:
        if scratch is not None:
            self.releases += 1
            self._free.setdefault(scratch.key, []).append(scratch)

    def stats(self) -> dict:
        return {"grid_allocs": self.grid_allocs, "acquires": self.acquires,
                "releases": self.releases,
                "free": sum(len(v) for v in self._free.values())}

    def clear(self) -> None:
        self._free.clear()


_POOL = _ScratchPool()

_ARANGE_CACHE: dict = {}


def _cached_arange(n: int) -> np.ndarray:
    """Read-only ``arange(n, dtype=int64)`` shared across fast-path plans."""
    a = _ARANGE_CACHE.get(n)
    if a is None:
        a = np.arange(n, dtype=np.int64)
        a.setflags(write=False)
        _ARANGE_CACHE[n] = a
    return a


def scratch_stats() -> dict:
    """Pool counters for the allocation-regression test."""
    return _POOL.stats()


def release_plan(plan: "RoutePlan") -> None:
    """Return a plan's scratch set to the pool (idempotent per scratch;
    callers must not release the same plan twice)."""
    _POOL.release(plan.scratch)


# ---------------------------------------------------------------------------
# Stage 1: host-side device split (numpy, outside jit).
# ---------------------------------------------------------------------------


class RoutePlan(NamedTuple):
    """Stage-1 output: per-group sub-batches + the metadata to invert them.

    d_ops/d_keys/d_vals  (D, Bd) np.int32 sub-batches in device order,
                         padded with OP_NOP / key 0 (exact no-ops)
    slot                 i64[B]: flat index into the (D, Bd) plane per
                         original lane (stage-1 never drops: always >= 0
                         for real lanes; OP_NOP input lanes get -1 and are
                         not transported)
    groups               D
    lane_budget          adaptive stage-2 budget L (static)
    max_occ              realized max per-shard occupancy (real lanes)
    occupancy            i64[S] realized occupancy per storage row
    scratch              pooled buffer set backing d_ops/d_keys/d_vals/slot
                         (None when the plan owns its arrays); recycled by
                         :func:`release_plan` once the batch is forced
    """
    d_ops: np.ndarray
    d_keys: np.ndarray
    d_vals: np.ndarray
    slot: np.ndarray
    groups: int
    lane_budget: int
    max_occ: int
    occupancy: np.ndarray
    scratch: object = None


def host_route(sspec, ops: np.ndarray, keys: np.ndarray,
               values: np.ndarray) -> RoutePlan:
    """Stage 1: split a B-lane mixed batch into D per-device sub-batches by
    shard-id high bits (storage-row block), measuring per-shard occupancy
    along the way.  Pure numpy -- runs before (outside) the jitted
    program, which is what removes the all-gather: each device's program
    is handed ONLY its own lanes.

    Lane order is preserved inside every sub-batch, so per-shard lane
    priority downstream equals global lane priority.  ``OP_NOP`` input
    lanes (caller padding) are not transported at all -- they are exact
    no-ops with result False by definition.

    The (D, Bd) grids and the slot map come from the per-geometry scratch
    pool; the plan's ``scratch`` handle is recycled (``release_plan``)
    once the batch has been forced, so steady-state routing performs no
    grid allocation.
    """
    with span("registry.route"):
        ops = np.asarray(ops, np.int32)
        keys = np.asarray(keys, np.int32)
        values = np.asarray(values, np.int32)
        b = int(keys.shape[0])
        s = sspec.n_shards
        d = resolve_groups(sspec)
        per = s // d

        row = _np_row_of(keys, sspec, d)
        real = ops != OP_NOP
        occupancy = np.bincount(row[real], minlength=s)
        max_occ = int(occupancy.max()) if b else 0
        lane_budget = adaptive_lane_budget(sspec, max(b, 1), max_occ)

        if d == 1 and b and real.all():
            # single-group, no caller padding: the sub-batch IS the batch
            # (order preserved) -- skip the split/scatter, but still pad to
            # the pow2 Bd bucket so live shapes match what precompile traced
            bd = _pow2_at_least(b)
            sc = _POOL.acquire(1, bd, b)
            sc.d_ops[0, :b] = ops
            sc.d_ops[0, b:] = OP_NOP
            sc.d_keys[0, :b] = keys
            sc.d_keys[0, b:] = 0
            sc.d_vals[0, :b] = values
            sc.d_vals[0, b:] = 0
            return RoutePlan(sc.d_ops, sc.d_keys, sc.d_vals,
                             _cached_arange(b), 1, lane_budget, max_occ,
                             occupancy, sc)

        gid = row // per
        counts = np.bincount(gid[real], minlength=d)
        bd = _pow2_at_least(max(int(counts.max()) if b else 0, 1))

        sc = _POOL.acquire(d, bd, b)
        d_ops, d_keys, d_vals, slot = (sc.d_ops, sc.d_keys, sc.d_vals,
                                       sc.slot)
        d_ops.fill(OP_NOP)
        d_keys.fill(0)
        d_vals.fill(0)
        slot.fill(-1)
        if b:
            # stable group-major order; rank within group = sub-batch
            # position
            lanes = np.flatnonzero(real)
            order = lanes[np.argsort(gid[lanes], kind="stable")]
            g_sorted = gid[order]
            seg0 = np.searchsorted(g_sorted, np.arange(d))
            rank = np.arange(order.size) - seg0[g_sorted]
            d_ops[g_sorted, rank] = ops[order]
            d_keys[g_sorted, rank] = keys[order]
            d_vals[g_sorted, rank] = values[order]
            slot[order] = g_sorted.astype(np.int64) * bd + rank
        return RoutePlan(d_ops, d_keys, d_vals, slot, d, lane_budget,
                         max_occ, occupancy, sc)


def host_gather(grid, slot: np.ndarray, fill) -> np.ndarray:
    """Invert stage 1 for per-lane results: (D, Bd) -> [B], ``fill`` for
    lanes that were never transported (OP_NOP input padding)."""
    flat = np.asarray(grid).reshape(-1)
    if flat.size == 0:
        return np.full(slot.shape, fill, dtype=np.asarray(fill).dtype)
    got = flat[np.clip(slot, 0, flat.size - 1)]
    return np.where(slot >= 0, got, fill)


# ---------------------------------------------------------------------------
# Stage 2: in-jit per-device sort/segment router over the LOCAL shards.
# ---------------------------------------------------------------------------


def _local_row(keys: jax.Array, sspec, n_groups: int) -> jax.Array:
    """Local shard row (within the device's block) per key, from hash32
    bits alone -- stage 1 already guaranteed the lane belongs to this
    device, so the group offset cancels out of the storage-row formula."""
    s = sspec.n_shards
    per = s // n_groups
    if per == 1:
        return jnp.zeros(keys.shape, jnp.int32)
    sbits = s.bit_length() - 1
    sid = (hash32(keys) >> jnp.uint32(32 - sbits)).astype(jnp.int32)
    if sspec.placement == "contiguous" or n_groups <= 1:
        return sid & (per - 1)             # low log2(S/D) bits of sid
    return sid >> (n_groups.bit_length() - 1)   # strided: row = sid // D


def route_local(ops: jax.Array, keys: jax.Array, values: jax.Array, *,
                sspec, n_groups: int, lane_budget: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                           jax.Array]:
    """Stage 2: one device's (Bd,) sub-batch -> its (S/D, L) local lane
    grid.  Same stable sort/segment scheme as the v1 router; OP_NOP
    padding lanes are parked on a virtual overflow row so they never
    consume budget.  Returns ``(r_ops, r_keys, r_vals, slot, dropped)``
    with ``slot[i] == -1`` for dropped/padding lanes; ``dropped`` counts
    REAL lanes past the budget (only possible under a ``max_lane_budget``
    cap)."""
    bd = keys.shape[0]
    per = sspec.n_shards // n_groups
    lane = lane_budget
    local = _local_row(keys, sspec, n_groups)
    local = jnp.where(ops == OP_NOP, per, local)        # park padding
    order = jnp.argsort(local, stable=True)
    lsort = local[order]
    idx = jnp.arange(bd, dtype=jnp.int32)
    seg0 = jnp.full((per + 1,), bd, jnp.int32).at[lsort].min(idx)
    pos = idx - seg0[lsort]                             # rank in local shard
    keep = (pos < lane) & (lsort < per)
    flat = jnp.where(keep, lsort * lane + pos, per * lane)   # OOB == drop

    def scatter(x, fill):
        return jnp.full((per * lane,), fill, jnp.int32).at[flat].set(
            x[order], mode="drop").reshape(per, lane)

    r_ops = scatter(ops, OP_NOP)
    r_keys = scatter(keys, 0)
    r_vals = scatter(values, 0)
    slot = jnp.full((bd,), -1, jnp.int32).at[order].set(
        jnp.where(keep, flat, -1))
    dropped = jnp.sum((~keep & (ops[order] != OP_NOP)).astype(jnp.int32))
    return r_ops, r_keys, r_vals, slot, dropped


def _grid_gather(grid: jax.Array, slot: jax.Array, fill) -> jax.Array:
    """Inverse of :func:`route_local` for per-lane results."""
    flat = grid.reshape(-1)
    got = flat[jnp.clip(slot, 0, flat.shape[0] - 1)]
    return jnp.where(slot >= 0, got, fill)


# ---------------------------------------------------------------------------
# Jitted dispatch: per-device program (stage 2 + vmapped shard apply),
# executed under shard_map when the group count matches the mesh, plain
# vmap over the group axis otherwise (logical grouping, e.g. in tests).
# ---------------------------------------------------------------------------


def _use_mesh(sspec, groups: int) -> bool:
    return bool(sspec.use_shard_map) and groups > 1 \
        and groups == mesh_devices(sspec)


def _group_dispatch(group_fn, state, lanes, *, sspec, groups: int):
    """Run ``group_fn(state_block, *lane_rows)`` once per device group.

    Under ``shard_map`` every array argument/output is partitioned on
    dim0 over the 1-D ("shards",) mesh -- the per-device program sees
    ONLY its (S/D, ...) state block and its (Bd,) lanes, so no collective
    can appear in the compiled module.  Without a matching mesh the same
    body runs under vmap over a reshaped (D, S/D, ...) state.
    """
    s = sspec.n_shards
    per = s // groups
    if _use_mesh(sspec, groups):
        def body(st, *rows):
            st, *outs = group_fn(st, *(r[0] for r in rows))
            return (st,) + tuple(o[None] for o in outs)

        mesh = jax.make_mesh((groups,), ("shards",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        p = PartitionSpec("shards")
        return jax.shard_map(body, mesh=mesh, in_specs=p, out_specs=p,
                             check_vma=False)(state, *lanes)
    stacked = jax.tree.map(
        lambda x: x.reshape((groups, per) + x.shape[1:]), state)
    out = jax.vmap(group_fn)(stacked, *lanes)
    state = jax.tree.map(
        lambda x: x.reshape((s,) + x.shape[2:]), out[0])
    return (state,) + tuple(out[1:])


def _packed_row(st, lanes, codes, dropped) -> jax.Array:
    """One group's row of the packed output: ``lanes`` (values, for
    ``get``), the (Bd,) lane codes ``result | kept << 1``, the group's
    dropped count, and the group's overflow latch AFTER this batch
    (``any`` over its local shards).  The latch is reduced per group, so
    under ``shard_map`` the row stays on its device: the host ORs the D
    latches and no collective enters the program."""
    tail = jnp.stack([dropped.astype(jnp.int32),
                      jnp.any(st.overflow).astype(jnp.int32)])
    return jnp.concatenate(lanes + (codes, tail))


def _codes(res: jax.Array, kept: jax.Array) -> jax.Array:
    return res.astype(jnp.int32) | (kept.astype(jnp.int32) << 1)


@functools.partial(jax.jit,
                   static_argnames=("sspec", "groups", "lane_budget"),
                   donate_argnums=(0,))
def _apply_v2(state, d_ops: jax.Array, d_keys: jax.Array,
              d_vals: jax.Array, *, sspec, groups: int, lane_budget: int):
    """Device-local mixed-op dispatch: per device, stage-2 route the (Bd,)
    sub-batch into the (S/D, L) local grid and execute the local shards
    in one vmapped ``apply_batch_impl``.  Returns (stacked state, packed
    i32[D, Bd + 2]): per group, the lane codes ``result | kept << 1``
    (kept is False exactly for the real lanes stage 2 dropped past a
    ``max_lane_budget`` cap, so callers can retry/reshard instead of
    reading a dropped lane as a successful no-op), the dropped count and
    the overflow latch -- everything the host needs, in ONE read."""
    spec = sspec.shard_spec()

    def group_fn(st, o, k, v):
        r_ops, r_keys, r_vals, slot, dropped = route_local(
            o, k, v, sspec=sspec, n_groups=groups, lane_budget=lane_budget)
        fn = functools.partial(E.apply_batch_impl, spec=spec)
        st, r_res = jax.vmap(fn)(st, r_ops, r_keys, r_vals)
        kept = (slot >= 0) | (o == OP_NOP)
        return st, _packed_row(
            st, (), _codes(_grid_gather(r_res, slot, False), kept), dropped)

    return _group_dispatch(group_fn, state,
                           (d_ops, d_keys, d_vals), sspec=sspec,
                           groups=groups)


@functools.partial(jax.jit,
                   static_argnames=("sspec", "groups", "lane_budget",
                                    "default"),
                   donate_argnums=(0,))
def _get_v2(state, d_keys: jax.Array, d_active: jax.Array, *, sspec,
            groups: int, lane_budget: int, default: int = 0):
    """Device-local value lookup; same routing as :func:`_apply_v2`.
    Returns (stacked state, packed i32[D, 2*Bd + 2]): per group the (Bd,)
    values, then the codes ``present | kept << 1``, dropped, overflow."""
    spec = sspec.shard_spec()

    def group_fn(st, k, act):
        ops = jnp.where(act, OP_CONTAINS, OP_NOP)
        r_ops, r_keys, _, slot, dropped = route_local(
            ops, k, k, sspec=sspec, n_groups=groups,
            lane_budget=lane_budget)
        fn = functools.partial(E.get_impl, spec=spec, default=default)
        st, r_vals, r_pres = jax.vmap(
            lambda s_, k_, a_: fn(s_, k_, active=a_))(
                st, r_keys, r_ops == OP_CONTAINS)
        vals = _grid_gather(r_vals, slot, jnp.int32(default))
        pres = _grid_gather(r_pres, slot, False)
        kept = (slot >= 0) | ~act
        return st, _packed_row(st, (vals.astype(jnp.int32),),
                               _codes(pres, kept), dropped)

    return _group_dispatch(group_fn, state, (d_keys, d_active),
                           sspec=sspec, groups=groups)


# ---------------------------------------------------------------------------
# Host entrypoints (stage 1 + jitted stage 2/dispatch + host gather-back).
#
# The dispatch is ASYNC at the JAX level: the jitted program returns
# device futures immediately, so the synchronous entrypoints are the
# async ones forced on the spot, and the pipelined path simply defers the
# force.  Because every routing artifact is volatile (NVTraverse:
# traverse volatile, persist the destination), deferring the gather-back
# changes no durability obligation -- psyncs happen inside the jitted
# program in exactly the same order either way.
# ---------------------------------------------------------------------------


_KEPT = np.int32(2)        # lane-code bit: the lane was not dropped


class InFlight:
    """A dispatched-but-unforced v2 batch.

    Holds the packed device output of the jitted stage-2 program plus the
    stage-1 :class:`RoutePlan` needed to invert it.  ``force()`` performs
    the batch's one host sync (a single read inside its
    ``registry.sync.batch`` span), returns the per-lane numpy results,
    sets ``overflow``, and recycles the plan's scratch set.  ``kind`` is
    "apply" (``force() -> (results bool[B], dropped, drop_mask
    bool[B])``) or "get" (``force() -> (values i32[B], present bool[B],
    dropped, drop_mask bool[B])``).  ``drop_mask[i]`` is True exactly
    when real lane i was shed past a ``max_lane_budget`` cap -- its
    result is NOT a successful no-op and the caller must retry or
    reshard (all-False on every drop-free trace; OP_NOP padding is never
    "dropped").  ``overflow`` is the map's overflow latch after this
    batch (any shard), known once forced; None for an empty batch, which
    ran no program.
    """
    __slots__ = ("kind", "plan", "outs", "default", "overflow", "_forced")

    def __init__(self, kind: str, plan: RoutePlan, outs, default: int = 0):
        self.kind = kind
        self.plan = plan
        self.outs = outs          # packed device future, or None if empty
        self.default = default
        self.overflow = None
        self._forced = None

    @property
    def forced(self) -> bool:
        return self._forced is not None

    def force(self):
        if self._forced is None:
            with span("registry.force"):
                self._forced = self._gather()
                self.outs = None
                _POOL.release(self.plan.scratch)
        return self._forced

    def _gather(self):
        if self.outs is None:
            empty = np.zeros((0,), bool)
            if self.kind == "apply":
                return empty, 0, empty
            return np.zeros((0,), np.int32), empty, 0, empty
        with span("registry.sync.batch"):
            packed = np.asarray(self.outs)
        width = packed.shape[1] - 2
        bd = width if self.kind == "apply" else width // 2
        slot = self.plan.slot
        dropped = int(packed[:, -2].sum())
        self.overflow = bool(packed[:, -1].any())
        # untransported OP_NOP lanes read as result False, kept
        codes = host_gather(packed[:, width - bd:width], slot, _KEPT)
        hit, drop_mask = (codes & 1).astype(bool), (codes & _KEPT) == 0
        if self.kind == "apply":
            return hit, dropped, drop_mask
        return (host_gather(packed[:, :bd], slot, np.int32(self.default)),
                hit, dropped, drop_mask)


def dispatch_plan(state, plan: RoutePlan, *, sspec, kind: str = "apply",
                  default: int = 0):
    """Launch the jitted stage-2 program for a stage-1 plan (no host
    sync).  Returns ``(state futures, InFlight)``; an empty plan is a
    no-op whose scratch is recycled immediately."""
    if plan.slot.size == 0:
        _POOL.release(plan.scratch)
        return state, InFlight(kind, plan._replace(scratch=None), None,
                               default)
    with span("registry.launch"):
        if kind == "apply":
            state, packed = _apply_v2(
                state, jnp.asarray(plan.d_ops), jnp.asarray(plan.d_keys),
                jnp.asarray(plan.d_vals), sspec=sspec, groups=plan.groups,
                lane_budget=plan.lane_budget)
        else:
            state, packed = _get_v2(
                state, jnp.asarray(plan.d_keys),
                jnp.asarray(plan.d_ops) == OP_CONTAINS, sspec=sspec,
                groups=plan.groups, lane_budget=plan.lane_budget,
                default=default)
        return state, InFlight(kind, plan, packed, default)


def apply_batch_v2_async(state, ops, keys, values, *, sspec):
    """Two-stage routed mixed-op batch WITHOUT the host sync: stage 1
    routes on the host, stage 2 is dispatched, and the gather-back is
    deferred to ``InFlight.force()``.  Returns ``(state, InFlight)``."""
    plan = host_route(sspec, ops, keys, values)
    return dispatch_plan(state, plan, sspec=sspec, kind="apply")


def get_v2_async(state, keys, *, sspec, default: int = 0):
    """Async two-stage value lookup; see :func:`apply_batch_v2_async`."""
    keys = np.asarray(keys, np.int32)
    ops = np.full(keys.shape, OP_CONTAINS, np.int32)
    plan = host_route(sspec, ops, keys, keys)
    return dispatch_plan(state, plan, sspec=sspec, kind="get",
                         default=default)


def apply_batch_v2(state, ops, keys, values, *, sspec):
    """Two-stage routed mixed-op batch.  Returns ``(state, results
    bool[B] (numpy), dropped int, drop_mask bool[B], the forced
    InFlight)`` -- its ``plan`` and ``overflow`` latch.  Linearization
    and psync accounting are bit-identical to the v1 single-stage router
    (same lanes, same per-shard order)."""
    state, fl = apply_batch_v2_async(state, ops, keys, values, sspec=sspec)
    out, dropped, drop_mask = fl.force()
    return state, out, dropped, drop_mask, fl


def get_v2(state, keys, *, sspec, default: int = 0):
    """Two-stage routed value lookup.  Returns ``(state, values i32[B],
    present bool[B], dropped int, drop_mask bool[B], the forced
    InFlight)``."""
    state, fl = get_v2_async(state, keys, sspec=sspec, default=default)
    out_v, out_p, dropped, drop_mask = fl.force()
    return state, out_v, out_p, dropped, drop_mask, fl


def precompile(state, batch: int, *, sspec, partial=None):
    """Pre-compile the stage-2 program for every budget the adaptive
    chooser can select for a B-lane batch (the "small set of pre-compiled
    power-of-two budgets").  Executes all-NOP sub-batches -- exact no-ops
    on the state (no psyncs, no n_ops).  For D > 1 the realized Bd is
    next_pow2(max group count), which for a near-balanced split lands on
    either next_pow2(ceil(B/D)) or one bucket above it (the max of D
    multinomial counts routinely exceeds B/D), so BOTH shapes are traced.

    ``partial`` (default: on iff ``sspec.pipeline_depth > 1``) ALSO
    traces every smaller pow2 Bd bucket a padded batch can realize: a
    pipelined serving loop pads short waves with ``OP_NOP`` lanes, which
    stage 1 does not transport, so the realized Bd shrinks below the
    full-batch bucket and an untraced shape would stall the pipeline
    mid-serve exactly when overlap matters.  For each smaller bucket only
    the budgets actually reachable at that occupancy (max_occ <= D*Bd)
    are traced, so the sweep stays near-linear in log2(B) rather than
    quadratic.  Returns (state, budgets traced for the full batch)."""
    b = max(int(batch), 1)
    d = resolve_groups(sspec)
    if partial is None:
        partial = getattr(sspec, "pipeline_depth", 1) > 1
    budgets = budget_candidates(sspec, b)
    bd_full = _pow2_at_least(-(-b // d))
    bds = {bd_full: budgets}
    if d > 1:
        bds[min(2 * bd_full, _pow2_at_least(b))] = budgets
    if partial:
        bd = bd_full // 2
        while bd >= 1:
            # a shard's occupancy never exceeds its group's lane count,
            # which the bucket bounds by bd -- sweep only that far
            reach = tuple(sorted({
                adaptive_lane_budget(sspec, b, 1 << i)
                for i in range(bd.bit_length() + 1)}))
            bds.setdefault(bd, reach)
            bd //= 2
    for bd in sorted(bds):
        nop = jnp.full((d, bd), OP_NOP, jnp.int32)
        zero = jnp.zeros((d, bd), jnp.int32)
        for lane in bds[bd]:
            state, _ = _apply_v2(state, nop, zero, zero, sspec=sspec,
                                 groups=d, lane_budget=lane)
            state, _ = _get_v2(state, zero, nop == OP_CONTAINS,
                               sspec=sspec, groups=d, lane_budget=lane,
                               default=0)
    return state, budgets
