"""Sharded DurableMap: hash-partitioned shard runtime (DESIGN.md §6).

The paper's durable hash table scales because hash-splitting the key space
makes threads rarely collide (per-bucket lock-free lists, Section 5); the
same composition holds one level up: S *independent* durable sets, each with
its own node pool and volatile index, multiply capacity and throughput while
preserving the per-partition psync story (SOFT stays at 1 psync per update
*per shard* -- psync cost is additive across partitions, so the global bound
is unchanged).  Crash and recovery compose the same way: each shard's
volatile index is rebuilt independently, so recovery is embarrassingly
parallel -- the paper's parallel-recovery claim at the subsystem level.

Layout:

  partitioning  shard id = the HIGH ``log2(S)`` bits of ``hash32(key)``.
                The in-shard structures consume the LOW bits (bucket index,
                probe table), so shard routing is independent of in-shard
                placement -- no correlated collisions.
  state         one stacked :class:`SetState` pytree with a leading shard
                axis: every leaf of the per-shard state gains dim0 == S.
                Probe/scan/bucket backends (including the Pallas kernels)
                run under the stack unchanged.
  routing       router="v2" (default): the TWO-STAGE device-local router of
                :mod:`repro.core.router` -- stage 1 splits the batch into
                per-device sub-batches host-side (so no all-gather exists
                under ``shard_map``) and stage 2 sort/segment-routes each
                device's lanes into its (S/D, L) local grid with an
                ADAPTIVE lane budget L = next_pow2(realized max shard
                occupancy); drops happen only under an explicit
                ``max_lane_budget`` cap.  router="v1" keeps the legacy
                single-stage :func:`route`: the global (S, L) grid with the
                static L ~ lane_factor*B/S budget, dropping a shard's
                excess lanes past L (result False, counted, warned once --
                detectable, never silent).  On any trace where neither
                router drops (every within-budget workload) both execute
                the same lanes in the same per-shard order: results,
                state, and psync counters are bit-identical
                (tests/test_router_v2.py).  Under budget pressure the
                DROP SETS differ by design: v1's static budget sheds
                skew that uncapped v2 widens L to absorb.
  placement     when S >> D devices, ``ShardSpec.placement`` selects which
                shards co-locate: "contiguous" blocks (storage row ==
                global shard id) or "strided" interleaving -- a pure
                storage-row permutation (DESIGN.md §6).
  dispatch      ALL shards execute in ONE vmapped ``apply_batch_impl``
                dispatch (v2: one per device group).  With
                ``use_shard_map=True`` and more than one device, the
                per-device program is partitioned over a 1-D device mesh
                via ``shard_map`` (each device owns S/D shards); semantics
                are identical because shards never communicate.
  recovery      ``crash_and_recover`` draws an independent adversary ``u``
                per shard and rebuilds every volatile index in one vmapped
                ``recover_impl`` dispatch (the Pallas ``recovery_scan``
                kernel runs batched over the shard axis).

:class:`ShardedDurableMap` mirrors the :class:`DurableMap` API exactly
(insert / remove / contains / get / apply / crash_and_recover / psyncs /
ops / len / overflowed), so every index backend and driver works under
sharding unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core import durable_set as DS
from repro.core import engine as E
from repro.core import router as RT
from repro.core.durable_set import SetState
from repro.core.engine import (MetricsMixin, OP_CONTAINS, OP_INSERT, OP_NOP,
                               OP_REMOVE, SetSpec)
from repro.core.nvm import hash32, np_hash32
from repro.obs.metrics import span


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Frozen configuration of a sharded durable map (static jit arg).

    base            per-map :class:`SetSpec`; ``base.capacity`` is the
                    TOTAL capacity, split evenly across shards (every other
                    knob -- mode, backend, geometry -- applies per shard)
    n_shards        shard count S (power of two: routing takes the high
                    ``log2(S)`` bits of ``hash32``)
    router          "v2" (default): the two-stage device-local router with
                    adaptive lane budgets (:mod:`repro.core.router`);
                    "v1": the legacy single-stage global sort/segment
                    router with the static ``lane_factor`` budget
    placement       shard->device storage order when S >> D: "contiguous"
                    (device d owns the shard-id block, storage row ==
                    global shard id -- the v1 layout) or "strided"
                    (device d owns shards {d, d+D, d+2D, ...})
    lane_factor     v1 only: head-room multiplier sizing the per-shard
                    lane budget L(B) = next_pow2(lane_factor * ceil(B/S))
    min_lane_budget lower clamp on L; batches of B <= min_lane_budget get
                    L == B, i.e. routing can never drop a lane
    max_lane_budget v2 only: upper cap on the adaptive budget (0 = uncapped,
                    the default -- the adaptive router then NEVER drops).
                    With a cap, a shard receiving more lanes drops the
                    excess (counted + warned, like v1 past its budget)
    n_device_groups v2 only: explicit stage-1 group count D (0 = auto:
                    the mesh size under ``use_shard_map``, else 1).  A
                    non-mesh group count is dispatched with vmap -- the
                    logical two-stage split for tests/CI on one device
    pipeline_depth  v2 only: depth of the double-buffered dispatch
                    pipeline through :class:`ShardedDurableMap` (1 = the
                    default fully synchronous behavior).  At depth k the
                    facade keeps the newest batch STAGED host-side
                    (stage-1 routed, not yet dispatched) and up to k-1
                    dispatched batches un-forced, so stage 1 of batch
                    n+1 runs on the host while batch n executes on
                    device and results gather back lazily.  Results,
                    state, and psync counters are bit-identical to
                    depth 1 (tests/test_pipeline.py); a crash abandons
                    only the staged (never-dispatched, zero-psync) batch
    use_shard_map   partition the vmapped dispatch over a 1-D device mesh
                    of the largest power-of-two device count (<= S) the
                    process has; on one device that is plain vmap.
                    ``chip_smoke.py --four-chips`` requires the 4-device
                    mesh and fails instead of running on fewer devices
    """
    base: SetSpec
    n_shards: int = 8
    router: str = "v2"
    placement: str = "contiguous"
    lane_factor: int = 2
    min_lane_budget: int = 32
    max_lane_budget: int = 0
    n_device_groups: int = 0
    pipeline_depth: int = 1
    use_shard_map: bool = False

    def __post_init__(self):
        s = self.n_shards
        if s < 1 or (s & (s - 1)) != 0:
            raise ValueError(f"n_shards must be a power of two, got {s}")
        if self.router not in ("v1", "v2"):
            raise ValueError(f"router must be 'v1' or 'v2', got "
                             f"{self.router!r}")
        if self.placement not in RT.PLACEMENTS:
            raise ValueError(f"placement must be one of {RT.PLACEMENTS}, "
                             f"got {self.placement!r}")
        if self.lane_factor < 1:
            raise ValueError("lane_factor must be >= 1")
        if self.min_lane_budget < 1:
            raise ValueError("min_lane_budget must be >= 1")
        if self.max_lane_budget < 0:
            raise ValueError("max_lane_budget must be >= 0 (0 = uncapped)")
        g = self.n_device_groups
        if g < 0 or (g & (g - 1)) != 0:
            raise ValueError("n_device_groups must be 0 (auto) or a power "
                             f"of two, got {g}")
        if g > s:
            raise ValueError(f"n_device_groups ({g}) cannot exceed "
                             f"n_shards ({s})")
        if self.pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1, got "
                             f"{self.pipeline_depth}")
        if self.base.capacity < self.n_shards:
            raise ValueError(
                f"base.capacity ({self.base.capacity}) must be >= n_shards "
                f"({self.n_shards}): every shard needs at least one slot")
        if self.router == "v1":
            # fail loudly instead of silently ignoring v2-only knobs
            for knob, neutral in (("placement", "contiguous"),
                                  ("max_lane_budget", 0),
                                  ("n_device_groups", 0),
                                  ("pipeline_depth", 1)):
                if getattr(self, knob) != neutral:
                    raise ValueError(
                        f"{knob} is a v2-only knob; the v1 router ignores "
                        f"it (got {knob}={getattr(self, knob)!r})")

    @property
    def per_shard_capacity(self) -> int:
        """Per-shard node-pool capacity.  An even split keeps the exact
        quotient; a non-divisible total rounds the ceil quotient UP to
        the next power of two -- the invariant-preserving value (probe
        tables, bucket counts, and the resize engine's positional
        migration all assume pow2-friendly per-shard pools), never a
        silent truncation.  ``effective_capacity`` surfaces the total
        actually provisioned."""
        per, rem = divmod(self.base.capacity, self.n_shards)
        if rem == 0:
            return per
        return 1 << max(0, per).bit_length()

    @property
    def effective_capacity(self) -> int:
        """TOTAL capacity actually provisioned: ``per_shard_capacity *
        n_shards``.  Equals ``base.capacity`` exactly when the split is
        even; otherwise the rounded-up total (>= ``base.capacity``),
        surfaced here instead of silently exceeding the request."""
        return self.per_shard_capacity * self.n_shards

    def shard_spec(self) -> SetSpec:
        """The per-shard SetSpec (``capacity == per_shard_capacity``)."""
        return dataclasses.replace(self.base,
                                   capacity=self.per_shard_capacity)

    def with_n_shards(self, n_shards: int) -> "ShardSpec":
        """The same per-shard geometry at a different shard count: the
        total capacity scales so every shard keeps ``per_shard_capacity``
        slots -- the invariant the positional split/merge migration of
        :mod:`repro.core.resize` relies on (child slot i is parent slot
        i, so per-shard pools must not change size across a resize)."""
        return dataclasses.replace(
            self, n_shards=n_shards,
            base=dataclasses.replace(
                self.base, capacity=self.per_shard_capacity * n_shards))

    def split_spec(self) -> "ShardSpec":
        """Child geometry of an S -> 2S split (per-shard capacity kept)."""
        return self.with_n_shards(self.n_shards * 2)

    def merge_spec(self) -> "ShardSpec":
        """Parent geometry of a 2S -> S merge (per-shard capacity kept)."""
        if self.n_shards < 2:
            raise ValueError("cannot merge below one shard")
        return self.with_n_shards(self.n_shards // 2)

    def lane_budget(self, batch: int) -> int:
        """Per-shard lane slots L for a B-lane batch (static: B is a trace-
        time shape).  Small batches route loss-free (L == B); large batches
        take L ~ lane_factor * B / S, the source of the sharded speedup."""
        if self.n_shards == 1 or batch <= self.min_lane_budget:
            return batch
        per = -(-batch // self.n_shards) * self.lane_factor
        return min(batch, 1 << max(per - 1, self.min_lane_budget - 1)
                   .bit_length())


# ---------------------------------------------------------------------------
# Partitioning + router
# ---------------------------------------------------------------------------


def shard_of(keys: jax.Array, n_shards: int) -> jax.Array:
    """Shard id per key: the high log2(S) bits of hash32 (the in-shard
    index consumes the low bits, so placement stays uncorrelated)."""
    if n_shards == 1:
        return jnp.zeros(keys.shape, jnp.int32)
    bits = n_shards.bit_length() - 1
    return (hash32(keys) >> jnp.uint32(32 - bits)).astype(jnp.int32)


def np_shard_of(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """Host-side twin of :func:`shard_of` (test oracles, pre-routing)."""
    keys = np.asarray(keys)
    if n_shards == 1:
        return np.zeros(keys.shape, np.int32)
    bits = n_shards.bit_length() - 1
    return (np_hash32(keys) >> np.uint32(32 - bits)).astype(np.int32)


def route(ops: jax.Array, keys: jax.Array, values: jax.Array, *,
          n_shards: int, lane_budget: int
          ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort/segment router: B mixed lanes -> an (S, L) per-shard lane grid.

    Lanes are stably argsorted by shard id -- stability keeps the original
    lane order inside every shard, so per-shard lane priority equals global
    lane priority (same-key lanes always share a shard).  Each lane lands at
    its rank within the shard's segment; ranks >= L are DROPPED (reported,
    not executed).  Unused slots carry ``OP_NOP`` / key 0 and are exact
    no-ops.

    Returns ``(r_ops, r_keys, r_values, slot, dropped)``: the (S, L) grids,
    the flat grid slot per original lane (-1 == dropped), and the dropped-
    lane count.
    """
    b = keys.shape[0]
    s, l = n_shards, lane_budget
    sid = shard_of(keys, s)
    order = jnp.argsort(sid, stable=True)
    ssort = sid[order]
    idx = jnp.arange(b, dtype=jnp.int32)
    seg0 = jnp.full((s,), b, jnp.int32).at[ssort].min(idx)   # segment starts
    pos = idx - seg0[ssort]                                  # rank in shard
    keep = pos < l
    flat = jnp.where(keep, ssort * l + pos, s * l)           # OOB == drop

    def scatter(x, fill):
        return jnp.full((s * l,), fill, jnp.int32).at[flat].set(
            x[order], mode="drop").reshape(s, l)

    r_ops = scatter(ops, OP_NOP)
    r_keys = scatter(keys, 0)
    r_vals = scatter(values, 0)
    slot = jnp.full((b,), -1, jnp.int32).at[order].set(
        jnp.where(keep, flat, -1))
    dropped = jnp.sum((~keep).astype(jnp.int32))
    return r_ops, r_keys, r_vals, slot, dropped


def gather(grid: jax.Array, slot: jax.Array, fill) -> jax.Array:
    """Inverse of :func:`route` for per-lane results: (S, L) -> [B], with
    ``fill`` for dropped lanes."""
    flat = grid.reshape(-1)
    got = flat[jnp.clip(slot, 0, flat.shape[0] - 1)]
    return jnp.where(slot >= 0, got, fill)


def np_v1_drop_mask(keys: np.ndarray, *, n_shards: int, lane_budget: int
                    ) -> np.ndarray:
    """Host twin of the v1 :func:`route` drop decision: True per lane iff
    its rank within its shard segment is past the budget.  Purely
    positional (v1 routes OP_NOP lanes like any other), so the mask sum
    equals the jitted ``dropped`` count exactly."""
    keys = np.asarray(keys, np.int32)
    b = keys.shape[0]
    sid = np_shard_of(keys, n_shards)
    order = np.argsort(sid, kind="stable")
    seg0 = np.searchsorted(sid[order], np.arange(n_shards))
    pos = np.arange(b) - seg0[sid[order]]
    mask = np.zeros((b,), bool)
    mask[order] = pos >= lane_budget
    return mask


# ---------------------------------------------------------------------------
# Stacked state + dispatch
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("sspec",))
def make_state(sspec: ShardSpec) -> SetState:
    """Stacked fresh state: every SetState leaf gains a leading shard axis
    (dim0 == S).  Each slice is exactly ``engine.make_state(shard_spec)``.
    One compiled program: built op by op it is ~50 host dispatches, which
    a snapshot restart would pay on every crash."""
    base = E.make_state(sspec.shard_spec())
    return jax.tree.map(
        lambda x: jnp.repeat(x[None], sspec.n_shards, axis=0), base)


def _mesh_devices(sspec: ShardSpec) -> int:
    """Devices the shard axis can split over: the largest power-of-two
    divisor of n_shards that the process has devices for (1 == plain vmap)."""
    return RT.mesh_devices(sspec)

def _dispatch(vfn, sspec: ShardSpec):
    """Wrap a shard-axis-vmapped function for execution: identity on a
    single device, ``shard_map`` over a 1-D ("shards",) mesh otherwise.
    Shards never communicate, so partitioning dim0 is semantics-preserving.
    """
    d = _mesh_devices(sspec)
    if d <= 1:
        return vfn
    mesh = jax.make_mesh((d,), ("shards",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    p = PartitionSpec("shards")
    return jax.shard_map(vfn, mesh=mesh, in_specs=p, out_specs=p,
                         check_vma=False)


def _apply_impl(state: SetState, ops: jax.Array, keys: jax.Array,
                values: jax.Array, *, sspec: ShardSpec
                ) -> Tuple[SetState, jax.Array, jax.Array]:
    """Route a mixed batch and execute every shard in ONE vmapped dispatch.
    Returns (stacked state, per-lane result, dropped-lane count)."""
    l = sspec.lane_budget(keys.shape[0])
    r_ops, r_keys, r_vals, slot, dropped = route(
        ops, keys, values, n_shards=sspec.n_shards, lane_budget=l)
    fn = functools.partial(E.apply_batch_impl, spec=sspec.shard_spec())
    state, r_res = _dispatch(jax.vmap(fn), sspec)(state, r_ops, r_keys,
                                                  r_vals)
    return state, gather(r_res, slot, False), dropped


@functools.partial(jax.jit, static_argnames=("sspec",), donate_argnums=(0,))
def apply_batch(state: SetState, ops: jax.Array, keys: jax.Array,
                values: jax.Array, *, sspec: ShardSpec
                ) -> Tuple[SetState, jax.Array, jax.Array]:
    """Sharded mixed-op batch: route + one vmapped dispatch.  Linearization
    is per shard (phase order with lane priority, DESIGN.md §4); shards are
    disjoint key spaces, so any interleaving of per-shard histories is a
    legal global history."""
    return _apply_impl(state, ops, keys, values, sspec=sspec)


@functools.partial(jax.jit, static_argnames=("sspec",), donate_argnums=(0,))
def insert(state: SetState, keys: jax.Array, values: jax.Array, *,
           sspec: ShardSpec) -> Tuple[SetState, jax.Array, jax.Array]:
    ops = jnp.full(keys.shape, OP_INSERT, jnp.int32)
    return _apply_impl(state, ops, keys, values, sspec=sspec)


@functools.partial(jax.jit, static_argnames=("sspec",), donate_argnums=(0,))
def remove(state: SetState, keys: jax.Array, *, sspec: ShardSpec
           ) -> Tuple[SetState, jax.Array, jax.Array]:
    ops = jnp.full(keys.shape, OP_REMOVE, jnp.int32)
    return _apply_impl(state, ops, keys, keys, sspec=sspec)


@functools.partial(jax.jit, static_argnames=("sspec",), donate_argnums=(0,))
def contains(state: SetState, keys: jax.Array, *, sspec: ShardSpec
             ) -> Tuple[SetState, jax.Array, jax.Array]:
    ops = jnp.full(keys.shape, OP_CONTAINS, jnp.int32)
    return _apply_impl(state, ops, keys, keys, sspec=sspec)


@functools.partial(jax.jit, static_argnames=("sspec", "default"),
                   donate_argnums=(0,))
def get(state: SetState, keys: jax.Array, *, sspec: ShardSpec,
        default: int = 0
        ) -> Tuple[SetState, jax.Array, jax.Array, jax.Array]:
    """Sharded value lookup: (state, values-or-default, present, dropped)."""
    l = sspec.lane_budget(keys.shape[0])
    ops = jnp.full(keys.shape, OP_CONTAINS, jnp.int32)
    r_ops, r_keys, _, slot, dropped = route(
        ops, keys, keys, n_shards=sspec.n_shards, lane_budget=l)
    fn = functools.partial(E.get_impl, spec=sspec.shard_spec(),
                           default=default)
    state, r_vals, r_pres = _dispatch(
        jax.vmap(lambda st, k, a: fn(st, k, active=a)), sspec)(
            state, r_keys, r_ops == OP_CONTAINS)
    vals = gather(r_vals, slot, jnp.int32(default))
    present = gather(r_pres, slot, False)
    return state, vals, present, dropped


# ---------------------------------------------------------------------------
# Router dispatch: v2 two-stage (default) vs the legacy v1 single stage.
# ---------------------------------------------------------------------------


def dispatch_batch(state: SetState, ops, keys, values, *, sspec: ShardSpec
                   ) -> Tuple[SetState, jax.Array, int, np.ndarray,
                              Optional[RT.InFlight]]:
    """Route + execute a mixed batch through the spec's router.  Returns
    ``(state, per-lane results, dropped count, per-lane drop mask,
    forced v2 batch-or-None)`` -- the batch carries its stage-1 ``plan``
    and the ``overflow`` latch its one read brought back.
    ``drop_mask[i]`` is True exactly when lane i was shed past the lane
    budget -- its result is NOT a successful no-op; callers retry or
    reshard (all-False on drop-free traces).
    The v2 path runs stage 1 host-side (no all-gather under shard_map)
    and picks the adaptive lane budget; v1 is the single-stage global
    router.  Results/state/psyncs are bit-identical between the two
    (``tests/test_router_v2.py``)."""
    if sspec.router == "v1":
        b = np.asarray(keys).shape[0]
        state, res, dropped = apply_batch(
            state, jnp.asarray(ops, jnp.int32), jnp.asarray(keys, jnp.int32),
            jnp.asarray(values, jnp.int32), sspec=sspec)
        with span("registry.sync.dropped"):
            d = int(dropped)
        mask = np_v1_drop_mask(
            keys, n_shards=sspec.n_shards,
            lane_budget=sspec.lane_budget(b)) if d else np.zeros((b,), bool)
        return state, res, d, mask, None
    return RT.apply_batch_v2(state, ops, keys, values, sspec=sspec)


def dispatch_get(state: SetState, keys, *, sspec: ShardSpec,
                 default: int = 0):
    """Value lookup through the spec's router; returns ``(state, values,
    present, dropped, drop_mask, forced v2 batch-or-None)``."""
    if sspec.router == "v1":
        b = np.asarray(keys).shape[0]
        state, vals, present, dropped = get(
            state, jnp.asarray(keys, jnp.int32), sspec=sspec,
            default=default)
        with span("registry.sync.dropped"):
            d = int(dropped)
        mask = np_v1_drop_mask(
            keys, n_shards=sspec.n_shards,
            lane_budget=sspec.lane_budget(b)) if d else np.zeros((b,), bool)
        return state, vals, present, d, mask, None
    return RT.get_v2(state, keys, sspec=sspec, default=default)


# ---------------------------------------------------------------------------
# Crash + parallel recovery
# ---------------------------------------------------------------------------


def crash(state: SetState, u: jax.Array
          ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Power failure across all shards.  ``u`` is the per-shard adversary,
    (S, N_shard) in [0, 1); the stage-machine crash is elementwise, so the
    stacked state needs no explicit vmap."""
    return DS.crash(state, u)


@functools.partial(jax.jit, static_argnames=("sspec",))
def recover(persisted: jax.Array, keys: jax.Array, values: jax.Array,
            stamp: Optional[jax.Array] = None, *,
            sspec: ShardSpec) -> Tuple[SetState, jax.Array]:
    """Parallel recovery: every shard's classification scan + volatile-index
    rebuild runs in ONE vmapped dispatch (the Pallas ``recovery_scan``
    kernel batches over the shard axis).  Returns (stacked state, per-shard
    stage histogram i32[S, 5])."""
    fn = functools.partial(E.recover_impl, spec=sspec.shard_spec())
    if stamp is None:
        return _dispatch(jax.vmap(
            lambda p, k, v: fn(p, k, v)), sspec)(persisted, keys, values)
    return _dispatch(jax.vmap(fn), sspec)(persisted, keys, values, stamp)


def _delta_width(max_slots: int) -> int:
    """The padded delta width of a hybrid recovery whose fullest shard has
    ``max_slots`` delta slots: the next power of two, at least 8."""
    return max(8, 1 << max(0, int(max_slots) - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("sspec", "snap_overflowed"),
                   donate_argnums=(0,))
def hybrid_recover(snap: SetState, persisted: jax.Array, keys: jax.Array,
                   values: jax.Array, stamp: jax.Array,
                   delta_idx: jax.Array, *, sspec: ShardSpec,
                   snap_overflowed: bool = False) -> SetState:
    """Per-shard snapshot + delta-log recovery in ONE vmapped dispatch:
    every leading axis is the shard axis (``delta_idx`` is (S, D), padded
    per shard with the shard capacity; ``snap_overflowed``: any shard's
    stored latch).  Bit-identical to :func:`recover` on the same crash
    planes (DESIGN.md §11)."""
    fn = functools.partial(E.hybrid_recover_impl, spec=sspec.shard_spec(),
                           snap_overflowed=snap_overflowed)
    return _dispatch(jax.vmap(fn), sspec)(snap, persisted, keys, values,
                                          stamp, delta_idx)


# ---------------------------------------------------------------------------
# OO façade (mirrors DurableMap exactly)
# ---------------------------------------------------------------------------


class _LazyBatch:
    """Deferred per-lane results of a pipelined batch (array-like).

    Returned by :class:`ShardedDurableMap` mutators/lookups when
    ``pipeline_depth > 1``.  Reading it -- ``np.asarray``, iteration,
    indexing, ``.value()`` -- forces the pipeline up to and including
    this batch, which is the only host sync on the pipelined path.  A
    crash that strikes while the batch is still STAGED (stage-1 routed
    but never dispatched) abandons it: the batch never executed and paid
    zero psyncs, so recovery legitimately drops it; reading an abandoned
    handle raises ``RuntimeError``.
    """
    __slots__ = ("_owner", "_kind", "_plan", "_default", "_inflight",
                 "_value", "_present", "_dropped", "_drop_mask",
                 "_abandoned")

    def __init__(self, owner, kind: str, plan, default: int = 0):
        self._owner = owner
        self._kind = kind                 # "apply" | "get"
        self._plan = plan
        self._default = default
        self._inflight = None             # set when dispatched
        self._value = None
        self._present = None
        self._dropped = None
        self._drop_mask = None
        self._abandoned = False

    @property
    def abandoned(self) -> bool:
        return self._abandoned

    def value(self) -> np.ndarray:
        """Per-lane results (forces the pipeline through this batch)."""
        if self._abandoned:
            raise RuntimeError(
                "pipelined batch was abandoned by a crash before dispatch "
                "(never executed, zero psyncs); re-submit it after recovery")
        if self._value is None:
            self._owner._force_through(self)
        return self._value

    @property
    def present(self) -> np.ndarray:
        """For get batches: the per-lane presence mask (forces)."""
        self.value()
        return self._present

    @property
    def dropped(self) -> int:
        """Router-dropped lane count for this batch (forces)."""
        self.value()
        return self._dropped

    @property
    def drop_mask(self) -> np.ndarray:
        """Per-lane drop mask for this batch (forces): True exactly for
        the lanes shed past a ``max_lane_budget`` cap, whose results are
        NOT successful no-ops -- retry or reshard them."""
        self.value()
        return self._drop_mask

    def __array__(self, dtype=None, copy=None):
        v = np.asarray(self.value())
        return v.astype(dtype) if dtype is not None else v

    def __iter__(self):
        return iter(self.value())

    def __len__(self):
        return len(self.value())

    def __getitem__(self, i):
        return self.value()[i]

    def __repr__(self):
        if self._abandoned:
            return "_LazyBatch(abandoned)"
        if self._value is None:
            stage = "staged" if self._inflight is None else "in-flight"
            return f"_LazyBatch({self._kind}, {stage})"
        return f"_LazyBatch({self._kind}, forced={self._value!r})"


class ShardedDurableMap(MetricsMixin):
    """DurableMap façade over S independent shards (single-controller).

    >>> m = ShardedDurableMap(SetSpec(capacity=65536, backend="bucket"),
    ...                       n_shards=8)
    >>> m.insert([1, 2], [10, 20])
    >>> m.contains([1, 3])          # -> [True, False]
    >>> m.crash_and_recover()       # per-shard adversary, vmapped rebuild

    Every backend registered with the engine works unchanged.  Routing past
    the lane budget drops lanes (counted in ``router_dropped``, warned once,
    result False) -- impossible for batches of <= ``min_lane_budget`` lanes.
    """

    def __init__(self, spec=None, n_shards: Optional[int] = None,
                 metrics=None, metrics_name: str = "sharded_map",
                 **spec_kwargs):
        if isinstance(spec, ShardSpec):
            if n_shards is not None:
                spec_kwargs["n_shards"] = n_shards
            sspec = dataclasses.replace(spec, **spec_kwargs) \
                if spec_kwargs else spec
        else:
            shard_kw = {k: spec_kwargs.pop(k)
                        for k in ("router", "placement", "lane_factor",
                                  "min_lane_budget", "max_lane_budget",
                                  "n_device_groups", "pipeline_depth",
                                  "use_shard_map")
                        if k in spec_kwargs}
            if spec is None:
                spec = SetSpec(**spec_kwargs)
            elif spec_kwargs:
                spec = dataclasses.replace(spec, **spec_kwargs)
            sspec = ShardSpec(base=spec,
                              n_shards=8 if n_shards is None else n_shards,
                              **shard_kw)
        E.get_backend(sspec.base.backend)     # fail fast
        sspec.shard_spec()                    # validate per-shard geometry
        self.sspec = sspec
        self.state = make_state(sspec)
        self.last_recovery_hist = None        # i32[5], summed over shards
        self.last_recovery_hist_shards = None  # i32[S, 5]
        self.router_dropped = 0
        self.last_route = None                # v2: stage-1 RoutePlan
        self.last_drop_mask = None            # bool[B] of the last batch
        self.pipeline_abandoned = 0           # staged batches lost to crash
        self._staged = None                   # routed, not yet dispatched
        self._pending = []                    # dispatched, not yet forced
        self._overflow_warned = False
        self._dropped_warned = False
        self._m_name = metrics_name
        if metrics is not None:
            self.attach_metrics(metrics, name=metrics_name)

    @property
    def spec(self) -> SetSpec:
        """The per-shard SetSpec actually executing."""
        return self.sspec.shard_spec()

    @property
    def n_shards(self) -> int:
        return self.sspec.n_shards

    @property
    def overflowed(self) -> bool:
        """True once ANY shard latched its index overflow (see
        ``DurableMap.overflowed``)."""
        self._dispatch_staged()
        with span("registry.sync.overflow"):
            return bool(self.state.overflow.any())

    def _finish(self, res, dropped, drop_mask=None, forced=None):
        if drop_mask is not None:
            self.last_drop_mask = drop_mask
        d = int(dropped)
        if d:
            self.router_dropped += d
            if not self._dropped_warned:
                self._dropped_warned = True
                knob = ("raise or clear max_lane_budget"
                        if self.sspec.router == "v2" else
                        "raise lane_factor")
                E.warn_structure(
                    f"ShardedDurableMap dropped {d} lane(s): a shard "
                    f"received more than the lane budget; {knob} "
                    f"or submit smaller batches (sspec={self.sspec})",
                    stacklevel=4)
        # the overflow latch lives in device state.  A forced v2 batch
        # (``forced``, an InFlight) brought it back in its one packed
        # read; an empty one ran no program, so its latch (None) cannot
        # have moved.  Without one (router v1, a recheck) reading the
        # latch is a sync of its own
        if not self._overflow_warned and (
                self.overflowed if forced is None else bool(forced.overflow)):
            self._overflow_warned = True
            E.warn_structure(self._overflow_message(), stacklevel=4)
        return res

    def _overflow_message(self) -> str:
        """Warning text for the one-shot overflow latch.  A wrapping
        facade (ElasticShardedMap) rebinds this per instance so the
        warning names the remedy the wrapper actually offers."""
        return (f"ShardedDurableMap index overflow latched on a shard "
                f"(spec={self.spec}); lookups may miss live keys -- grow "
                "capacity, stash_size, or n_shards")

    # -- double-buffered pipeline (pipeline_depth > 1) ---------------------
    #
    # The newest batch is STAGED (stage-1 routed host-side, not yet
    # dispatched); up to depth-1 older batches are dispatched but not yet
    # forced.  Submitting batch n first pushes the staged batch n-1 to the
    # device (async), then runs stage 1 of batch n on the host WHILE the
    # device executes -- the double buffering the ROADMAP calls for.
    # Batch order is strictly FIFO, so linearization, results, state, and
    # psync counters are bit-identical to the synchronous path
    # (tests/test_pipeline.py).  A crash abandons only the staged batch:
    # it never executed and paid zero psyncs, so recovery drops exactly
    # the uncommitted in-flight work and nothing else.

    def _submit(self, kind, ops, keys, values, default: int = 0):
        self._dispatch_staged()               # batch n-1 starts executing
        if kind == "get":
            keys = np.asarray(keys, np.int32)
            ops = np.full(keys.shape, OP_CONTAINS, np.int32)
            values = keys
        plan = RT.host_route(self.sspec, ops, keys, values)  # overlaps
        handle = _LazyBatch(self, kind, plan, default)
        self._staged = handle
        self.last_route = plan
        while len(self._pending) > self.sspec.pipeline_depth - 1:
            self._force_oldest()
        return handle

    def _dispatch_staged(self):
        h = self._staged
        if h is None:
            return
        self._staged = None
        self.state, h._inflight = RT.dispatch_plan(
            self.state, h._plan, sspec=self.sspec, kind=h._kind,
            default=h._default)
        self._pending.append(h)

    def _force_oldest(self):
        h = self._pending.pop(0)
        out = h._inflight.force()
        if h._kind == "apply":
            h._value, h._dropped, h._drop_mask = out
        else:
            h._value, h._present, h._dropped, h._drop_mask = out
        self._finish(h._value, h._dropped, h._drop_mask, h._inflight)

    def _force_through(self, handle):
        """Force the pipeline, in submit order, through ``handle``."""
        if handle is self._staged:
            self._dispatch_staged()
        while self._pending and handle._value is None \
                and not handle._abandoned:
            self._force_oldest()

    def pipeline_flush(self):
        """Dispatch the staged batch and force every pending batch (each
        brings its overflow latch to ``_finish``).  The no-op on a
        synchronous map."""
        self._dispatch_staged()
        while self._pending:
            self._force_oldest()
        return self

    def scratch_stats(self) -> dict:
        """Routing scratch-pool counters (module-wide ``_ScratchPool``):
        ``grid_allocs`` (real buffer allocations), ``acquires``,
        ``releases`` (recycles -- including the scratch of a batch
        ABANDONED by ``crash_and_recover``), ``free`` (sets parked in
        the pool).  ``acquires - releases`` is the number of scratch
        sets still referenced by staged/in-flight batches; after a
        ``pipeline_flush`` or a crash it is exactly the pre-existing
        in-flight count -- nothing leaks (tests/test_obs.py)."""
        return RT.scratch_stats()

    def _recheck_overflow(self):
        # the sharded overflow check lives in _finish
        self._finish(None, 0)

    def _metrics_extra(self) -> dict:
        route = None
        if self.last_route is not None:
            route = {"lane_budget": self.last_route.lane_budget,
                     "groups": self.last_route.groups,
                     "max_occ": self.last_route.max_occ}
        return {
            "n_shards": self.n_shards,
            "router_dropped": self.router_dropped,
            "pipeline_abandoned": self.pipeline_abandoned,
            "pipeline_staged": int(self._staged is not None),
            "pipeline_pending": len(self._pending),
            "scratch": self.scratch_stats(),
            "last_route": route,
        }

    def _apply(self, ops, keys, values):
        if self.sspec.pipeline_depth > 1:
            return self._submit("apply", ops, keys, values)
        self.state, res, dropped, drop_mask, fl = dispatch_batch(
            self.state, ops, keys, values, sspec=self.sspec)
        if fl is not None:
            self.last_route = fl.plan
        return self._finish(res, dropped, drop_mask, fl)

    def insert(self, keys, values=None):
        keys = np.asarray(keys, np.int32)
        values = keys if values is None else np.asarray(values, np.int32)
        return self._apply(np.full(keys.shape, OP_INSERT, np.int32), keys,
                           values)

    def remove(self, keys):
        keys = np.asarray(keys, np.int32)
        return self._apply(np.full(keys.shape, OP_REMOVE, np.int32), keys,
                           keys)

    def contains(self, keys):
        keys = np.asarray(keys, np.int32)
        return self._apply(np.full(keys.shape, OP_CONTAINS, np.int32), keys,
                           keys)

    def get(self, keys, default: int = 0):
        """Values for present keys, ``default`` otherwise."""
        if self.sspec.pipeline_depth > 1:
            return self._submit("get", None, keys, None, default)
        self.state, vals, _, dropped, drop_mask, fl = dispatch_get(
            self.state, np.asarray(keys, np.int32), sspec=self.sspec,
            default=default)
        if fl is not None:
            self.last_route = fl.plan
        return self._finish(vals, dropped, drop_mask, fl)

    def apply(self, ops, keys, values=None):
        """Mixed contains/insert/remove batch; see :func:`apply_batch`."""
        keys = np.asarray(keys, np.int32)
        values = keys if values is None else np.asarray(values, np.int32)
        return self._apply(np.asarray(ops, np.int32), keys, values)

    def precompile(self, batch: int, partial=None):
        """Trace/compile the v2 stage-2 program for every lane budget the
        adaptive chooser can pick for ``batch``-lane batches (exact no-op
        on the map's contents).  ``partial`` (default: on iff
        ``pipeline_depth > 1``) also covers every smaller pow2 Bd bucket
        a padded batch can realize, so neither the first pipelined wave
        nor an open-loop driver serving short padded batches ever pays a
        trace stall mid-serve.  Returns the tuple of budgets compiled."""
        if self.sspec.router != "v2":
            return ()
        self._dispatch_staged()               # keep FIFO order intact
        self.state, budgets = RT.precompile(self.state, batch,
                                            sspec=self.sspec,
                                            partial=partial)
        return budgets

    def _pre_crash(self):
        """Shared crash prologue: ABANDON the staged batch (stage-1 routed
        but never dispatched -- it executed nothing and paid zero psyncs),
        force every already-dispatched batch (their psyncs were issued
        inside the jitted program: committed work), and fold the device
        counters that the rebuild is about to reset."""
        if self._staged is not None:
            h, self._staged = self._staged, None
            RT.release_plan(h._plan)
            h._abandoned = True
            self.pipeline_abandoned += 1
            if self._m is not None:
                self._m.counter(
                    f"{self._m_name}.pipeline_abandoned").inc()
        while self._pending:
            self._force_oldest()
        self._metrics_pre_recovery()          # counters are about to reset

    def crash_and_recover(self, u=None, seed: int = 0):
        """Crash all shards and rebuild in one vmapped recovery dispatch.
        ``u`` defaults to an INDEPENDENT uniform adversary per shard.

        Pipelined maps: a batch still STAGED at crash time was never
        dispatched -- it executed nothing and paid zero psyncs, so it is
        ABANDONED (its handle raises on read, ``pipeline_abandoned``
        counts it) and recovery proceeds without it.  Already-dispatched
        batches are committed work: their psyncs were issued inside the
        jitted program, so they are forced (completing normally) before
        the crash is applied -- exactly the crash-at-any-point semantics
        of the synchronous path.
        """
        with span("registry.recover"):
            self._pre_crash()
            if u is None:
                u = np.random.default_rng(seed).random(
                    self.state.cur.shape).astype(np.float32)
            t0 = time.perf_counter()
            with span("registry.crash"):
                crashed = crash(self.state, jnp.asarray(u))
            with span("registry.rebuild"):
                self.state, hist = recover(*crashed, sspec=self.sspec)
            with span("registry.sync.recover_hist"):
                self.last_recovery_hist_shards = np.asarray(hist)
            self.last_recovery_hist = self.last_recovery_hist_shards.sum(
                axis=0)
            with span("registry.sync.recover_ready"):
                jax.block_until_ready(self.state.keys)  # honest timing
            self.last_recovery_seconds = time.perf_counter() - t0
            self._metrics_post_recovery(
                scanned_slots=self.n_shards * self.spec.capacity)
            self._post_recovery_overflow()  # latch recomputed; re-armed
        return self

    # --- snapshot + delta-log hybrid recovery (DESIGN.md §11) -----------
    #
    # Identical watermark discipline to ``DurableMap``, vectorized over the
    # shard axis: the watermark is an (S,) epoch vector, the delta list an
    # (S, D) grid padded per shard, and the recovery ONE vmapped dispatch.

    _SNAP_FIELDS = E.DurableMap._SNAP_FIELDS

    @property
    def supports_hybrid(self) -> bool:
        return E.supports_hybrid_recovery(self.spec)

    def snapshot_capture(self) -> dict:
        """Flush the pipeline to a clean dispatch boundary, host-copy the
        stacked durable planes, and open a new stamp generation on every
        shard.  Zero psyncs -- a pure NVM read (``cur == flushed`` holds
        per shard at the boundary)."""
        self.pipeline_flush()
        cap = {
            "watermark": np.asarray(self.state.epoch).copy(),   # (S,)
            "raw_stage": np.asarray(self.state.flushed),
            "keys": np.asarray(self.state.keys),
            "values": np.asarray(self.state.values),
            "stamp": np.asarray(self.state.stamp),
        }
        self.state = self.state._replace(epoch=self.state.epoch + 1)
        return cap

    def snapshot_build(self, cap: dict):
        """Canonicalize the capture with the normal vmapped ``recover``
        (background-thread safe).  Returns (planes, meta); every plane
        keeps its leading shard axis."""
        st, hist = recover(jnp.asarray(cap["raw_stage"]),
                           jnp.asarray(cap["keys"]),
                           jnp.asarray(cap["values"]),
                           jnp.asarray(cap["stamp"]), sspec=self.sspec)
        jax.block_until_ready(st.keys)
        planes = {f: np.asarray(getattr(st, f)) for f in self._SNAP_FIELDS}
        planes["raw_stage"] = cap["raw_stage"]
        meta = {"kind": "sharded_map",
                "watermark": cap["watermark"].tolist(),
                "hist": np.asarray(hist).tolist()}
        return planes, meta

    def _snapshot_state(self, planes: dict) -> SetState:
        cur = jnp.asarray(planes["cur"])
        return make_state(self.sspec)._replace(
            keys=jnp.asarray(planes["keys"]),
            values=jnp.asarray(planes["values"]),
            cur=cur, flushed=cur,
            stamp=jnp.asarray(planes["stamp"]),
            bkeys=jnp.asarray(planes["bkeys"]),
            bids=jnp.asarray(planes["bids"]),
            skeys=jnp.asarray(planes["skeys"]),
            sids=jnp.asarray(planes["sids"]),
            stash_n=jnp.asarray(planes["stash_n"]),
            size=jnp.asarray(planes["size"]),
            overflow=jnp.asarray(planes["overflow"]))

    def hybrid_crash_and_recover(self, planes: dict, meta: dict, u=None,
                                 seed: int = 0):
        """Crash all shards and recover from the stored snapshot + each
        shard's stamp delta in ONE vmapped dispatch; bit-identical to
        ``crash_and_recover`` under the same adversary.  Staged-batch
        abandonment follows the same rules.  Recovery psyncs: exactly 0.
        Spans as ``crash_and_recover``'s, with ``registry.sync.delta``
        (one read of the crash-time stamp and stage planes),
        ``registry.delta`` (the host's delta index and histogram) and
        ``registry.snapshot.load`` (the snapshot planes put back on the
        device) before the rebuild."""
        with span("registry.recover"):
            self._pre_crash()
            if u is None:
                u = np.random.default_rng(seed).random(
                    self.state.cur.shape).astype(np.float32)
            n = self.spec.capacity
            w = np.asarray(meta["watermark"], np.int32).reshape(-1, 1)
            t0 = time.perf_counter()
            with span("registry.crash"):
                crashed = crash(self.state, jnp.asarray(u))
            with span("registry.sync.delta"):
                stamp, crash_stage = jax.device_get((crashed[3],
                                                     crashed[0]))
            with span("registry.delta"):
                mask = stamp > w                                  # (S, N)
                delta_idx = np.full((self.n_shards,
                                     _delta_width(mask.sum(axis=1).max())),
                                    n, np.int32)
                hist = np.asarray(meta["hist"], np.int64)         # (S, 5)
                raw = planes["raw_stage"]
                n_delta = 0
                for s in range(self.n_shards):
                    idx = np.flatnonzero(mask[s]).astype(np.int32)
                    delta_idx[s, :idx.size] = idx
                    n_delta += idx.size
                    hist[s] -= np.bincount(np.clip(raw[s, idx], 0, 4),
                                           minlength=5)
                    hist[s] += np.bincount(np.clip(crash_stage[s, idx], 0,
                                                   4), minlength=5)
            with span("registry.snapshot.load"):
                snap = self._snapshot_state(planes)
            ovf = bool(np.any(planes["overflow"]))
            with span("registry.rebuild"):
                self.state = hybrid_recover(snap, *crashed,
                                            jnp.asarray(delta_idx),
                                            sspec=self.sspec,
                                            snap_overflowed=ovf)
            self.last_recovery_hist_shards = hist.astype(np.int32)
            self.last_recovery_hist = self.last_recovery_hist_shards.sum(
                axis=0)
            with span("registry.sync.recover_ready"):
                jax.block_until_ready(self.state.keys)
            self.last_recovery_seconds = time.perf_counter() - t0
            total = self.n_shards * n
            self._metrics_post_recovery(
                scanned_slots=n_delta, from_snapshot=total - n_delta,
                from_delta=n_delta,
                patch=E.hybrid_patch_path(self.spec, delta_idx.shape[1],
                                          ovf))
            self._post_recovery_overflow()
        return self

    def precompile_hybrid(self, min_delta: int, max_delta: int) -> list:
        """Compile the hybrid recovery for every delta width a restart
        whose fullest shard has ``min_delta`` to ``max_delta`` delta slots
        can realise (the powers of two between their widths), so that no
        such restart compiles.  Runs each on an all-padding delta over a
        copy of the live planes; the map's state is left as it was.
        Returns the widths."""
        planes = {f: np.asarray(getattr(self.state, f))
                  for f in self._SNAP_FIELDS}
        u = jnp.zeros(self.state.cur.shape, jnp.float32)
        crashed = crash(self.state, u)
        widths = []
        d = _delta_width(min_delta)
        while d <= _delta_width(max_delta):
            out = hybrid_recover(self._snapshot_state(planes), *crashed,
                                 jnp.full((self.n_shards, d),
                                          self.spec.capacity, jnp.int32),
                                 sspec=self.sspec)
            jax.block_until_ready(out.keys)
            widths.append(d)
            d *= 2
        return widths

    @property
    def psyncs(self):
        # dispatch the staged batch first so the counters reflect every
        # submitted batch -- identical to what a synchronous read would see
        self._dispatch_staged()
        return int(self.state.n_psync.sum())

    @property
    def ops(self):
        self._dispatch_staged()
        return int(self.state.n_ops.sum())

    def __len__(self):
        self._dispatch_staged()
        return int(self.state.size.sum())

    def __repr__(self):
        return (f"ShardedDurableMap(size={len(self)}, psyncs={self.psyncs}, "
                f"n_shards={self.n_shards}, spec={self.spec})")
