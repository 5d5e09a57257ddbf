"""DurableMap engine: SetSpec config + pluggable volatile-index backends.

This is the public surface of the durable-set reproduction (DESIGN.md §4).
The paper's central idea is the split between a durable node pool and a
*volatile* index that is rebuilt on recovery; this module makes that index a
first-class, swappable backend instead of a string threaded through every
call:

  probe    vectorized linear-probe hash lookup over ``SetState.table``
           (the default; pure lax, models the paper's hash-table runs)
  scan     O(N) traversal lookup (models the paper's linked-list runs)
  bucket   set-associative (NB buckets x W ways) index carried in
           ``SetState`` (DESIGN.md §5): built once at make_state/recovery,
           updated incrementally by the op bodies (O(B*W) scatter), and
           probed by the Pallas MXU kernel ``hash_probe.probe_pallas``;
           recovery runs the streaming Pallas kernel
           ``recovery_scan.scan_pallas``.  Live nodes that overflow a
           bucket land in an exact dense stash the lookup falls back to
           (gated on the stash-occupancy latch), so the backend is correct
           at any load factor.

Everything is configured by one frozen, hashable :class:`SetSpec` (capacity,
algorithm mode, backend, table/bucket geometry) that is passed as a static
jit argument -- no loose kwargs.  Pallas kernels run compiled on the TPU
and interpreted elsewhere (:func:`repro.kernels.resolve_interpret`).

The serving-shaped entrypoint is :func:`apply_batch`: a mixed
contains/insert/remove lane vector executed in ONE jitted dispatch.  Mixed
batches linearize phase-by-phase (all contains, then all inserts, then all
removes) with lane priority inside a phase -- the same deterministic
stand-in for CAS order the core uses (DESIGN.md §2).

:class:`DurableMap` is the OO façade; :class:`DurableSet` remains as a thin
deprecation shim over it.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
import time
import warnings
from typing import Dict, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import durable_set as DS
from repro.core.durable_set import SetState, MODES
from repro.core.nvm import FREE, VALID
from repro.kernels.hash_probe import ops as hp_ops
from repro.kernels.recovery_scan import ops as rs_ops
from repro.obs.metrics import span

# Mixed-batch op codes for apply_batch.  OP_NOP matches no phase, so a lane
# carrying it is an exact no-op (no state change, no psync, no n_ops, result
# False) -- the padding value the shard router fills unused lane slots with.
OP_CONTAINS, OP_INSERT, OP_REMOVE, OP_NOP = 0, 1, 2, 3


def warn_structure(message: str, stacklevel: int = 3) -> None:
    """Emit a one-shot-per-STRUCTURE RuntimeWarning.

    ``warnings.warn`` under the default filters dedups through the
    attributed caller's module ``__warningregistry__``, keyed on (message,
    category, lineno) -- MODULE-GLOBAL state.  Every durable structure
    warns from the same few call sites, so the first structure's overflow
    warning would swallow a second structure's first overflow in the same
    process (e.g. a queue-full warning after a map-overflow warning).
    Callers already latch one-shot per instance
    (``self._overflow_warned``); this helper emits through the normal
    filter machinery (an explicit "ignore"/"error" filter still applies)
    and then purges the registry entries the emission created, so the
    module-global dedup never swallows a LATER structure's first warning.

    ``stacklevel`` has the meaning it would have for a direct
    ``warnings.warn`` call from the caller, +1 for this helper's frame.
    """
    try:
        # the frame warnings.warn(stacklevel=N) attributes the warning to,
        # counted from this function's own frame: N-1 levels up.
        registry = sys._getframe(stacklevel - 1).f_globals.setdefault(
            "__warningregistry__", {})
        before = frozenset(registry)
    except ValueError:                        # stacklevel past the stack top
        registry, before = None, frozenset()
    try:
        warnings.warn(message, RuntimeWarning, stacklevel=stacklevel)
    finally:
        if registry is not None:
            for key in set(registry) - before:
                registry.pop(key, None)       # undo the dedup record

# Node-id budget of the MXU byte-plane gather: ids travel as three bytes
# (see hash_probe.kernel).
_ID_BUDGET = 1 << 24


@dataclasses.dataclass(frozen=True)
class SetSpec:
    """Frozen configuration of a durable map (hashable => static jit arg).

    capacity      node-pool size N (max live members)
    mode          psync algorithm: "soft" | "linkfree" | "logfree"
    backend       volatile-index backend name (see BACKENDS)
    table_factor  probe-table slots per node (power-of-2 rounded)
    max_probe     linear-probe cap for the probe table
    n_buckets     bucket backend: bucket count NB (0 => derived so the
                  table holds 2x capacity at width w: next pow2 of 2N/W)
    bucket_width  bucket backend: ways per bucket W
    stash_size    bucket backend: dense-stash slots S for per-bucket
                  overflow spill (overflowing past S latches
                  ``state.overflow``)
    use_pallas    run the Pallas kernels where the backend has them: the
                  bucket lookup/recovery path, and the probe backend's
                  windowed table lookup (else pure-lax references)
    probe_pallas_lookup
                  probe backend: route lookups through the Pallas
                  ``table_lookup`` one-hot-matmul path.  None (the
                  default) auto-selects by platform -- the MXU route on
                  TPU, the chunked lax window gather elsewhere (on CPU the
                  matmul sweep is strictly more work than the gather)
    """
    capacity: int
    mode: str = "soft"
    backend: str = "probe"
    table_factor: int = 4
    max_probe: int = 128
    n_buckets: int = 0
    bucket_width: int = 8
    stash_size: int = 128
    use_pallas: bool = True
    probe_pallas_lookup: Optional[bool] = None

    def __post_init__(self):
        if self.capacity <= 0:
            raise ValueError(f"capacity must be positive, got {self.capacity}")
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")
        for f in ("table_factor", "max_probe", "bucket_width", "stash_size"):
            if getattr(self, f) < 1:
                raise ValueError(f"{f} must be >= 1")
        if self.n_buckets < 0 or (self.n_buckets &
                                  (self.n_buckets - 1)) != 0:
            raise ValueError("n_buckets must be 0 (derived) or a power of "
                             f"two, got {self.n_buckets}")
        if self.backend == "bucket" and self.capacity >= _ID_BUDGET:
            raise ValueError("bucket backend: capacity exceeds the kernel's "
                             f"node-id budget ({_ID_BUDGET})")

    def bucket_geometry(self) -> Tuple[int, int]:
        """Resolved (NB, W) for the bucket backend."""
        w = self.bucket_width
        nb = self.n_buckets
        if nb == 0:
            target = max(8, -(-2 * self.capacity // w))   # ceil(2N / W)
            nb = 1 << (target - 1).bit_length()
        return nb, w


class IndexBackend(Protocol):
    """A volatile-index backend: lookup on the hot path, validity
    classification on the recovery path, plus the index-lifecycle hooks of
    DESIGN.md §5 (state geometry, bulk build, incremental maintenance).
    Register with :func:`register_backend`; implementations must be
    pure/jittable with ``spec`` static."""
    name: str
    # True => recovery bulk-builds the linear-probe table for this backend
    # (its lookups read ``SetState.table``).  Hot-path maintenance is NOT
    # keyed on this flag: it lives entirely in ``update_index``.
    builds_probe_table: bool

    def lookup(self, spec: SetSpec, state: SetState,
               keys: jax.Array) -> jax.Array:
        """Node id per query lane, or EMPTY (-1) when absent."""
        ...

    def recover_scan(self, spec: SetSpec, persisted: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
        """persisted stages i32[N] -> (member mask bool[N], stage hist i32[5])."""
        ...

    def state_geometry(self, spec: SetSpec) -> Tuple[int, int, int]:
        """(n_buckets, bucket_width, stash_size) sizing the SetState bucket
        fields -- (0, 0, 0) for backends that do not carry a bucket index."""
        ...

    def init_index(self, spec: SetSpec, state: SetState) -> SetState:
        """Bulk-build the backend's index fields from the node pool (state
        construction / recovery only -- never the hot path)."""
        ...

    def update_index(self, spec: SetSpec, phase: str
                     ) -> Optional[DS.IndexUpdateFn]:
        """The index commit hook for ``phase`` ("insert"|"remove"): a
        function ``(IndexFields, keys, node_ids, do) -> (IndexFields,
        overflow)`` updating exactly the index structures this backend owns
        (probe table, bucket planes, ...), or None when the mutation commits
        with no index maintenance.  This is the ONLY path by which the op
        bodies touch any volatile-index structure (DESIGN.md §2a)."""
        ...


class _NullIndexMixin:
    """Lifecycle defaults for backends without a carried bucket index."""

    def state_geometry(self, spec):
        return (0, 0, 0)

    def init_index(self, spec, state):
        return state

    def update_index(self, spec, phase):
        return None


class ProbeBackend(_NullIndexMixin):
    """The paper's hash-set experiments: linear probing over SetState.table.

    Reads route through the tiled Pallas ``hash_probe`` kernel when
    selected (``probe_pallas_lookup``; auto == TPU with a pool inside the
    kernel's node-id budget, capacity < 2^24; an explicit True past that
    budget raises): each lane's probe
    window is gathered once into a (B, P) plane pair and becomes its own
    bucket row, so probe shares the MXU one-hot matmul path the bucket
    backend uses.  Otherwise the chunked pure-lax window lookup runs --
    exact first-match semantics at a fraction of the gather volume.
    Writes commit through :func:`DS.probe_index_update` (``table_claim`` /
    ``table_release``)."""
    name = "probe"
    builds_probe_table = True

    def lookup(self, spec, state, keys):
        use = spec.probe_pallas_lookup
        if use is None:                # auto: MXU route on TPU only
            use = (spec.use_pallas and jax.default_backend() == "tpu"
                   and spec.capacity < _ID_BUDGET)
        if use:
            return hp_ops.table_lookup(state.table, state.keys, keys,
                                       max_probe=spec.max_probe)
        return DS._lookup_probe(state, keys, max_probe=spec.max_probe)

    def update_index(self, spec, phase):
        return DS.probe_index_update(phase, spec.max_probe)

    def recover_scan(self, spec, persisted):
        return rs_ops.recovery_scan(persisted, use_pallas=False)


class ScanBackend(_NullIndexMixin):
    """The paper's list experiments: cost dominated by full traversal."""
    name = "scan"
    builds_probe_table = False     # _lookup_scan reads cur/keys directly

    def lookup(self, spec, state, keys):
        return DS._lookup_scan(state, keys)

    def recover_scan(self, spec, persisted):
        return rs_ops.recovery_scan(persisted, use_pallas=False)


class BucketBackend:
    """Set-associative index carried in SetState, probed by the Pallas MXU
    kernel.

    Lifecycle (DESIGN.md §5): ``bucket_init`` bulk-packs live nodes into
    ``state.bkeys``/``state.bids`` at state construction and recovery;
    during operation ``bucket_insert``/``bucket_remove`` maintain the table
    with O(B*W) scatter writes (claim the first free way, free the way on
    delete, spill to the dense ``skeys``/``sids`` stash on per-bucket
    overflow).  Lookups
    are pure reads: ``hp_ops.lookup`` (probe_pallas when use_pallas) over
    the carried table, with an O(B*S) dense-stash fallback gated on the
    ``stash_n`` occupancy latch.  Recovery classification runs the
    streaming ``recovery_scan`` Pallas kernel.
    """
    name = "bucket"
    builds_probe_table = False

    def lookup(self, spec, state, keys):
        found = hp_ops.lookup(state.bkeys, state.bids, keys,
                              use_pallas=spec.use_pallas)

        def with_stash(f):
            # only paid while the stash is occupied (lax.cond branch)
            live = state.sids >= 0
            eq = live[None, :] & (keys[:, None] == state.skeys[None, :])
            hit = eq.any(axis=1)
            sid = state.sids[jnp.argmax(eq, axis=1).astype(jnp.int32)]
            return jnp.where((f < 0) & hit, sid, f)

        return lax.cond(state.stash_n > 0, with_stash, lambda f: f, found)

    def recover_scan(self, spec, persisted):
        return rs_ops.recovery_scan(persisted, use_pallas=spec.use_pallas)

    def state_geometry(self, spec):
        nb, w = spec.bucket_geometry()
        return nb, w, spec.stash_size

    def init_index(self, spec, state):
        nb, w = spec.bucket_geometry()
        bkeys, bids, skeys, sids, stash_n, ovf = hp_ops.bucket_init(
            state.keys, state.cur, nb=nb, w=w, s=spec.stash_size)
        return state._replace(bkeys=bkeys, bids=bids, skeys=skeys, sids=sids,
                              stash_n=stash_n,
                              overflow=state.overflow | ovf)

    def update_index(self, spec, phase):
        fn = hp_ops.bucket_insert if phase == "insert" \
            else hp_ops.bucket_remove

        def update(f: DS.IndexFields, keys, ids, do):
            bkeys, bids, skeys, sids, stash_n, ovf = fn(
                f.bkeys, f.bids, f.skeys, f.sids, f.stash_n, keys, ids, do)
            return f._replace(bkeys=bkeys, bids=bids, skeys=skeys,
                              sids=sids, stash_n=stash_n), ovf
        return update


BACKENDS: Dict[str, IndexBackend] = {}


def register_backend(backend: IndexBackend) -> IndexBackend:
    """Register an IndexBackend instance under ``backend.name``."""
    BACKENDS[backend.name] = backend
    return backend


def get_backend(name: str) -> IndexBackend:
    try:
        return BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown index backend {name!r}; registered: "
                       f"{sorted(BACKENDS)}") from None


register_backend(ProbeBackend())
register_backend(ScanBackend())
register_backend(BucketBackend())


def _lookup_fn(spec: SetSpec) -> DS.LookupFn:
    backend = get_backend(spec.backend)
    return functools.partial(backend.lookup, spec)


# ---------------------------------------------------------------------------
# Functional API (spec-static jitted ops).  ``state`` is donated on every
# entrypoint: the node-pool and bucket-table buffers are updated in place
# (where the platform supports donation) instead of copied per dispatch, so
# callers must rebind -- ``state, ok = insert(state, ...)``.
# ---------------------------------------------------------------------------


def make_state(spec: SetSpec) -> SetState:
    """Fresh spec-shaped state.  The bucket index is born empty-canonical
    (all ways EMPTY), which is exactly what ``init_index`` would build from
    an empty pool -- the ONLY other bulk build happens at recovery."""
    nb, w, s = get_backend(spec.backend).state_geometry(spec)
    return DS.make_state(spec.capacity, spec.table_factor, nb, w, s)


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def insert(state: SetState, keys: jax.Array, values: jax.Array, *,
           spec: SetSpec) -> Tuple[SetState, jax.Array]:
    backend = get_backend(spec.backend)
    return DS._insert_impl(state, keys, values, mode=spec.mode,
                           lookup_fn=_lookup_fn(spec),
                           index_update=backend.update_index(spec, "insert"))


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def remove(state: SetState, keys: jax.Array, *,
           spec: SetSpec) -> Tuple[SetState, jax.Array]:
    backend = get_backend(spec.backend)
    return DS._remove_impl(state, keys, mode=spec.mode,
                           lookup_fn=_lookup_fn(spec),
                           index_update=backend.update_index(spec, "remove"))


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def contains(state: SetState, keys: jax.Array, *,
             spec: SetSpec) -> Tuple[SetState, jax.Array]:
    state, present, _ = DS._contains_impl(state, keys, mode=spec.mode,
                                          lookup_fn=_lookup_fn(spec))
    return state, present


def get_impl(state: SetState, keys: jax.Array, *, spec: SetSpec,
             default: int = 0, active: Optional[jax.Array] = None
             ) -> Tuple[SetState, jax.Array, jax.Array]:
    """Unjitted get body (vmappable; the shard runtime maps it over the
    stacked shard axis).  ``active`` masks out lanes that must be exact
    no-ops (router padding)."""
    state, present, ids = DS._contains_impl(state, keys, mode=spec.mode,
                                            lookup_fn=_lookup_fn(spec),
                                            active=active)
    eidx = jnp.clip(ids, 0, state.values.shape[0] - 1)
    vals = jnp.where(present, state.values[eidx], jnp.int32(default))
    return state, vals, present


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def get(state: SetState, keys: jax.Array, *, spec: SetSpec,
        default: int = 0) -> Tuple[SetState, jax.Array, jax.Array]:
    """Value lookup: (state, values-or-default, present).  Read-path psync
    semantics are identical to contains (SOFT: free; others may flush)."""
    return get_impl(state, keys, spec=spec, default=default)


def apply_batch_impl(state: SetState, ops: jax.Array, keys: jax.Array,
                     values: jax.Array, *, spec: SetSpec
                     ) -> Tuple[SetState, jax.Array]:
    """Unjitted mixed-batch body: one contains->insert->remove phase sweep,
    each phase a plan/commit pipeline pass (DESIGN.md §2a).  Pure and
    vmappable -- :mod:`repro.core.shard` maps it over the stacked shard axis
    in ONE dispatch, so every backend's plan matrices and commit scatters
    shrink by ~S under sharding.  Lanes whose op code matches no phase
    (OP_NOP) are exact no-ops."""
    backend = get_backend(spec.backend)
    lookup_fn = _lookup_fn(spec)
    is_c = ops == OP_CONTAINS
    is_i = ops == OP_INSERT
    is_r = ops == OP_REMOVE
    state, r_c, ids = DS._contains_impl(state, keys, mode=spec.mode,
                                        lookup_fn=lookup_fn, active=is_c)
    # the contains phase only touches flushed/psync accounting, never the
    # index fields, so its lookup is still valid for the insert phase
    state, r_i = DS._insert_impl(
        state, keys, values, mode=spec.mode, lookup_fn=lookup_fn,
        active=is_i, existing=ids,
        index_update=backend.update_index(spec, "insert"))
    state, r_r = DS._remove_impl(
        state, keys, mode=spec.mode, lookup_fn=lookup_fn, active=is_r,
        index_update=backend.update_index(spec, "remove"))
    return state, jnp.where(is_i, r_i, jnp.where(is_r, r_r, r_c))


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def apply_batch(state: SetState, ops: jax.Array, keys: jax.Array,
                values: jax.Array, *, spec: SetSpec
                ) -> Tuple[SetState, jax.Array]:
    """Mixed-op batch in one jitted dispatch: the serving traffic shape.

    ``ops`` i32[B] of OP_CONTAINS / OP_INSERT / OP_REMOVE selects each
    lane's operation on ``keys``/``values``.  Linearization: the contains
    phase observes the pre-batch state, then inserts, then removes (so a
    remove lane deletes a key inserted by an earlier lane of the same
    batch), with lane priority inside each phase.  Returns success/presence
    per lane.
    """
    return apply_batch_impl(state, ops, keys, values, spec=spec)


def recover_impl(persisted: jax.Array, keys: jax.Array, values: jax.Array,
                 stamp: Optional[jax.Array] = None,
                 *, spec: SetSpec) -> Tuple[SetState, jax.Array]:
    """Unjitted recovery body (vmappable -- the shard runtime rebuilds all
    shards' volatile indexes in one vmapped dispatch).

    The overflow latch is RECOMPUTED here, never carried: the rebuilt
    state starts from a fresh ``make_state`` and ``state.overflow`` is
    re-derived from the rebuilt index alone (table build / init_index),
    so a spurious pre-crash latch does not survive a rebuild that no
    longer overflows, and a rebuild that DOES overflow latches anew.
    Facades pair this with ``MetricsMixin._post_recovery_overflow`` to
    re-arm the one-shot warning on the same boundary."""
    backend = get_backend(spec.backend)
    member, hist = backend.recover_scan(spec, persisted)
    nb, w, s = backend.state_geometry(spec)
    state = DS._rebuild_from_member(
        member, keys, values, spec.table_factor, spec.max_probe,
        n_buckets=nb, bucket_width=w, stash_size=s,
        build_table=backend.builds_probe_table,
        index_init=functools.partial(backend.init_index, spec),
        stamp=stamp)
    return state, hist


@functools.partial(jax.jit, static_argnames=("spec",))
def recover(persisted: jax.Array, keys: jax.Array, values: jax.Array,
            stamp: Optional[jax.Array] = None, *,
            spec: SetSpec) -> Tuple[SetState, jax.Array]:
    """Rebuild from the durable areas (Sections 3.5 / 4.6) through the
    spec's backend: classification via backend.recover_scan (the Pallas
    recovery_scan kernel for the bucket backend), then index rebuild --
    the one place besides state construction where the bucket index is
    bulk-built (``build_buckets`` via backend.init_index).
    Returns (state, stage histogram i32[5]) -- the recovery telemetry.
    No psync is ever issued: payloads are already durable."""
    return recover_impl(persisted, keys, values, stamp, spec=spec)


def crash_and_recover(state: SetState, u: jax.Array, *, spec: SetSpec
                      ) -> Tuple[SetState, jax.Array]:
    return recover(*DS.crash(state, u), spec=spec)


# ---------------------------------------------------------------------------
# Snapshot + delta-log hybrid recovery (DESIGN.md §11).
#
# A snapshot is the CANONICAL recovered state at a watermark W: the
# snapshotter captures the durable planes off the hot path, runs the normal
# ``recover`` on them (so the stored index is exactly what a full rebuild
# would produce), and persists the result.  Every durable commit stamps its
# slot with the current epoch inside the SAME scatter that moves the stage
# word, so ``stamp > W`` is a complete delta log that costs the mutation
# path zero extra psyncs.  Hybrid recovery then merges the crash-time
# planes into the snapshot at the delta slots only, and re-canonicalizes
# exactly the bucket rows those slots touch -- O(delta), bit-identical to
# the full-pool rebuild (bucket rows and the stash are pure functions of
# the member set in node-id order, see ``build_buckets``).
# ---------------------------------------------------------------------------


def supports_hybrid_recovery(spec: SetSpec) -> bool:
    """The probe backend's recovery table is built by SEQUENTIAL first-free
    claiming over the whole pool (``_table_write_ref``): a slot's final
    probe position depends on every earlier slot, so no O(delta) patch can
    be bit-identical.  Hybrid recovery supports the bucket and scan
    backends; probe falls back to the full rebuild."""
    return not get_backend(spec.backend).builds_probe_table


def hybrid_patch_path(spec: SetSpec, d: int,
                      snap_overflowed: bool = False) -> Optional[str]:
    """The bucket-index patch a hybrid restart of padded delta width ``d``
    takes: ``"delta"`` (O(delta): candidates from the snapshot's affected
    rows, its stash and the delta slots) while the candidate bound
    ``K = 2*d*w + s + d`` is below the capacity, else ``"full"`` (the
    whole-pool ``bucket_init`` on the merged planes, which at that width is
    the cheaper pass).  A snapshot whose stash overflowed keeps only the
    first ``s`` spills, so its index no longer names every member and it
    takes ``"full"`` too.  None for backends without a bucket index.
    Static: both inputs are known on the host before the call."""
    nb, w, s = get_backend(spec.backend).state_geometry(spec)
    if nb == 0:
        return None
    if snap_overflowed or 2 * d * w + s + d >= spec.capacity:
        return "full"
    return "delta"


def _delta_bucket_patch(snap: SetState, keys2, cur2, gi, valid, member_d,
                        delta_idx, *, spec: SetSpec):
    """Re-canonicalize exactly the bucket rows affected by the delta, in
    O(delta + stash) work.

    An affected bucket is one a delta slot's snapshot-time OR crash-time
    key hashes to.  Its live members after the crash are in the snapshot's
    row for it, in the snapshot's stash, or among the delta slots (the
    snapshot is canonical and did not overflow, see
    :func:`hybrid_patch_path`); an unaffected bucket's row and spills are
    unchanged.  One sort of those candidates by (bucket, node id) --
    behind one marker per affected bucket, id -1 -- drops the ids a row
    and the delta both name and gives each member its rank within its
    bucket in ascending-id order, exactly as ``build_buckets`` ranks it.
    Members of a group without a marker are the stash's unaffected
    spills.  The stash is then the ``s`` smallest spill ids, by a second
    sort of the same width, as ``bucket_init`` packs it.  Every gather,
    scatter and sort here is O(delta + stash) wide; the one pass over the
    whole table is the elementwise clear of the affected rows."""
    from repro.core.nvm import hash32, EMPTY
    n = spec.capacity
    nb, w = spec.bucket_geometry()
    s = spec.stash_size

    def bucket(k):
        return (hash32(k) % jnp.uint32(nb)).astype(jnp.int32)

    # affected buckets, nb where the slot was / is no member (2D, dups kept)
    ab = jnp.concatenate([
        jnp.where(valid & (snap.cur[gi] == VALID), bucket(snap.keys[gi]), nb),
        jnp.where(valid & member_d, bucket(keys2[gi]), nb)])
    # candidates: the affected rows (read way by way: a gather of whole
    # rows makes XLA relayout the table), the stash, the delta slots
    ways = jnp.arange(w, dtype=jnp.int32)
    rb = jnp.repeat(ab, w)
    rows = jnp.where(rb < nb, snap.bids[jnp.minimum(rb, nb - 1),
                                        jnp.tile(ways, ab.shape[0])], EMPTY)
    cid = jnp.concatenate([rows, snap.sids, delta_idx])
    cid = jnp.where((cid >= 0) & (cid < n), cid, n)
    cg = jnp.minimum(cid, n - 1)
    ck = keys2[cg]
    live = (cid < n) & (cur2[cg] == VALID)
    cb = jnp.concatenate([ab, jnp.where(live, bucket(ck), nb)])
    cid = jnp.concatenate([jnp.full(ab.shape, -1, jnp.int32),
                           jnp.where(live, cid, n)])
    ck = jnp.concatenate([jnp.zeros(ab.shape, ck.dtype), ck])
    sb, sid, sk = lax.sort((cb, cid, ck), num_keys=2, is_stable=False)

    pos = jnp.arange(sb.shape[0], dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), sb[1:] != sb[:-1]])
    dup = jnp.concatenate([jnp.zeros((1,), jnp.bool_), sid[1:] == sid[:-1]])
    member = (sb < nb) & (sid >= 0) & ~dup
    # a group is affected iff its first entry is a marker; rank = members
    # before this one in its group (cummax carries the group's start)
    aff = (lax.cummax(jnp.where(first, 2 * pos + (sid < 0), 0)) & 1) > 0
    m = member.astype(jnp.int32)
    before = jnp.cumsum(m) - m
    rank = before - lax.cummax(jnp.where(first, before, 0))
    in_row = member & aff & (rank < w)
    spilled = member & ~in_row

    tb = jnp.where(in_row, sb, nb)                   # OOB scatter => dropped
    tw = jnp.where(in_row, rank, 0)
    # an add, not a set: ab repeats buckets, and a set to repeated
    # indices compiles to one more sort
    cleared = (jnp.zeros((nb + 1,), jnp.int32).at[ab].add(1) > 0)[:nb, None]
    bkeys = jnp.where(cleared, 0, snap.bkeys).at[tb, tw].set(sk, mode="drop")
    bids = jnp.where(cleared, EMPTY, snap.bids) \
        .at[tb, tw].set(sid, mode="drop")

    spill = jnp.sum(spilled.astype(jnp.int32))
    first_s = lax.sort(jnp.where(spilled, sid, n), is_stable=False)[:s]
    got = first_s < n
    sids = jnp.where(got, first_s, EMPTY)
    skeys = jnp.where(got, keys2[jnp.minimum(first_s, n - 1)], 0)
    return bkeys, bids, skeys, sids, jnp.minimum(spill, s), spill > s


def hybrid_recover_impl(snap: SetState, persisted: jax.Array,
                        keys: jax.Array, values: jax.Array,
                        stamp: jax.Array, delta_idx: jax.Array,
                        *, spec: SetSpec,
                        snap_overflowed: bool = False) -> SetState:
    """Unjitted hybrid-recovery body (vmappable over a stacked shard axis).

    ``snap`` is the canonical snapshot state at watermark W;
    ``persisted``/``keys``/``values``/``stamp`` are the crash-time durable
    planes; ``delta_idx`` i32[D] lists the slots with ``stamp > W`` (padded
    with ``capacity``).  Slots outside the delta are bit-identical between
    capture and crash (every durable mutation stamps its slot inside the
    commit scatter), so classification -- the ``recovery_scan`` -- runs
    over the gathered delta only.  ``snap_overflowed`` (static: the stored
    latch, read on the host) and D choose the index patch
    (:func:`hybrid_patch_path`).  No psync is ever issued."""
    backend = get_backend(spec.backend)
    if backend.builds_probe_table:
        raise ValueError(
            f"backend {spec.backend!r} does not support hybrid recovery "
            "(sequential probe-table build has no canonical delta patch); "
            "use the full recover()")
    n = spec.capacity
    valid = delta_idx < n
    gi = jnp.where(valid, delta_idx, 0)
    # classification over the compacted delta only (padding -> stage FREE)
    member_d, _ = backend.recover_scan(
        spec, jnp.where(valid, persisted[gi], 0))
    member_d = member_d & valid

    scat = jnp.where(valid, delta_idx, n)           # OOB scatter => dropped
    keys2 = snap.keys.at[scat].set(
        jnp.where(member_d, keys[gi], 0), mode="drop")
    values2 = snap.values.at[scat].set(
        jnp.where(member_d, values[gi], 0), mode="drop")
    cur2 = snap.cur.at[scat].set(
        jnp.where(member_d, VALID, FREE), mode="drop")
    stamp2 = snap.stamp.at[scat].set(stamp[gi], mode="drop")
    was_member = valid & (snap.cur[gi] == VALID)
    size2 = snap.size + jnp.sum(member_d.astype(jnp.int32)) \
        - jnp.sum(was_member.astype(jnp.int32))

    state = snap._replace(
        keys=keys2, values=values2, cur=cur2, flushed=cur2, stamp=stamp2,
        size=size2,
        epoch=jnp.maximum(jnp.max(stamp2), 0) + 1,
    )
    path = hybrid_patch_path(spec, delta_idx.shape[0], snap_overflowed)
    if path is None:     # scan backend: no volatile index to patch
        return state._replace(overflow=jnp.zeros((), jnp.bool_))
    if path == "delta":
        index = _delta_bucket_patch(snap, keys2, cur2, gi, valid, member_d,
                                    scat, spec=spec)
    else:
        nb, w = spec.bucket_geometry()
        index = hp_ops.bucket_init(keys2, cur2, nb=nb, w=w,
                                   s=spec.stash_size)
    bkeys, bids, skeys, sids, stash_n, ovf = index
    return state._replace(bkeys=bkeys, bids=bids, skeys=skeys, sids=sids,
                          stash_n=stash_n, overflow=ovf)


@functools.partial(jax.jit, static_argnames=("spec", "snap_overflowed"),
                   donate_argnums=(0,))
def hybrid_recover(snap: SetState, persisted: jax.Array, keys: jax.Array,
                   values: jax.Array, stamp: jax.Array,
                   delta_idx: jax.Array, *, spec: SetSpec,
                   snap_overflowed: bool = False) -> SetState:
    """Jitted snapshot + delta-log recovery: O(delta) work on top of the
    restored snapshot, bit-identical to ``recover`` on the same crash
    planes (pinned by tests/test_snapshot.py)."""
    return hybrid_recover_impl(snap, persisted, keys, values, stamp,
                               delta_idx, spec=spec,
                               snap_overflowed=snap_overflowed)


def export_pool(state: SetState) -> dict:
    """Host copies of the DURABLE node-pool planes at a dispatch boundary
    (``cur == flushed`` holds there): the exact NVM content a migration,
    resharding, or snapshot reads.  Zero psyncs -- a pure read of already
    persisted planes.  Works on a per-shard state or a stacked (S, N)
    sharded state alike (the planes keep their leading axes)."""
    return {"stage": np.asarray(state.flushed),
            "keys": np.asarray(state.keys),
            "values": np.asarray(state.values),
            "stamp": np.asarray(state.stamp)}


def import_pool(planes: dict, *, spec: SetSpec) -> Tuple[SetState, jax.Array]:
    """Recovery-class bulk rebuild of ONE shard from raw pool planes (the
    :func:`export_pool` layout): classification scan + volatile-index
    build, exactly like crash recovery -- and like it, ZERO psyncs (the
    payloads being imported are already durable; only the destination
    bulk-persist of a migration pays, and that is accounted host-side by
    the caller as a recovery-class bulk persist, never per-op fences).
    Returns ``(state, stage histogram i32[5])``."""
    return recover(jnp.asarray(planes["stage"], jnp.int32),
                   jnp.asarray(planes["keys"], jnp.int32),
                   jnp.asarray(planes["values"], jnp.int32),
                   jnp.asarray(planes["stamp"], jnp.int32), spec=spec)


def pad_delta(idx: np.ndarray, capacity: int) -> np.ndarray:
    """Pad a host-side delta slot list to a power-of-two length >= 8 with
    ``capacity`` (the OOB-drop sentinel), so the gathered classification
    stays inside ``recovery_scan``'s tile divisibility and the number of
    distinct jit shapes is O(log N), not O(delta)."""
    idx = np.asarray(idx, np.int32)
    d = max(8, 1 << max(0, int(idx.size) - 1).bit_length())
    out = np.full((d,), capacity, np.int32)
    out[:idx.size] = idx
    return out


# ---------------------------------------------------------------------------
# OO façade
# ---------------------------------------------------------------------------


class MetricsMixin:
    """Observability plumbing shared by every durable-structure facade
    (DESIGN.md §10): ``DurableMap``, ``ShardedDurableMap``,
    ``DurableQueue``.

    Everything here is host-side and opt-in: with no registry attached a
    facade pays nothing, and even with one attached the device counters
    are only read inside ``_metrics_collect`` -- i.e. at registry
    SNAPSHOT time, an explicit force boundary -- never per dispatched
    batch.  The host class provides ``psyncs`` / ``ops`` / ``__len__`` /
    ``overflowed`` / ``last_recovery_hist`` and calls
    ``_metrics_pre_recovery`` (before applying a crash: the device
    counters are about to reset) and ``_metrics_post_recovery`` (after
    the rebuild) from its ``crash_and_recover``.
    """
    _m = None                       # MetricsRegistry (opt-in)
    _m_name = "structure"
    _m_bridge = None
    last_recovery_seconds = None

    def attach_metrics(self, registry, name: Optional[str] = None):
        """Register this structure's telemetry with a
        :class:`repro.obs.MetricsRegistry` under ``name``.  Returns
        self.  Device counters cross to the host only when the registry
        snapshots."""
        from repro.obs.bridge import DeviceCounterBridge
        if name is not None:
            self._m_name = name
        self._m = registry
        self._m_bridge = DeviceCounterBridge(registry, self._m_name)
        registry.register_collector(self._m_name, self._metrics_collect)
        return self

    def _metrics_extra(self) -> dict:
        """Subclass hook: structure-specific snapshot fields."""
        return {}

    def _metrics_collect(self) -> dict:
        b = self._m_bridge
        psyncs, ops = self.psyncs, self.ops
        b.fold(psync=psyncs, op=ops)
        out = {
            "psyncs": psyncs,                  # device counters (reset at
            "ops": ops,                        # recovery)
            "psync_total": b.total("psync"),   # monotone lifetime totals
            "ops_total": b.total("op"),
            "size": len(self),
            "overflowed": bool(self.overflowed),
            "recoveries":
                self._m.counter(f"{self._m_name}.recoveries").value,
            "recovery_psyncs":
                self._m.counter(f"{self._m_name}.recovery_psyncs").value,
        }
        if self.last_recovery_hist is not None:
            out["last_recovery_hist"] = np.asarray(
                self.last_recovery_hist).tolist()
            out["last_recovery_seconds"] = self.last_recovery_seconds
        out.update(self._metrics_extra())
        return out

    def _metrics_pre_recovery(self):
        """Fold the pre-crash counter deltas (they are about to reset)."""
        if self._m is not None:
            self._m_bridge.fold(psync=self.psyncs, op=self.ops)

    def _metrics_post_recovery(self, scanned_slots: int,
                               from_snapshot: int = 0,
                               from_delta: Optional[int] = None,
                               patch: Optional[str] = None):
        """Record the recovery: duration, scanned-slot gauges, and the
        recovery-psync counter (exactly 0 by construction -- payloads are
        already durable; the counter existing makes that checkable).

        ``scanned_slots`` is what the recovery CLASSIFIED (the
        ``recovery_scan`` input size); the split gauges attribute the
        recovered state to its sources: ``from_snapshot`` slots restored
        from the latest snapshot vs ``from_delta`` slots re-scanned because
        their stamp was newer than the watermark.  A full-pool recovery is
        all-delta (from_snapshot=0, from_delta=scanned_slots).  ``patch``
        names the bucket-index patch a hybrid restart took
        (:func:`hybrid_patch_path`), counted as
        ``<name>.hybrid_patch_delta`` or ``<name>.hybrid_patch_full``."""
        if self._m is None:
            return
        if from_delta is None:
            from_delta = scanned_slots
        m, name = self._m, self._m_name
        m.counter(f"{name}.recoveries").inc()
        m.counter(f"{name}.recovery_psyncs").inc(self.psyncs)
        if patch is not None:
            m.counter(f"{name}.hybrid_patch_{patch}").inc()
        m.gauge(f"{name}.last_recovery_scanned_slots").set(scanned_slots)
        m.gauge(f"{name}.last_recovery_from_snapshot_slots").set(
            from_snapshot)
        m.gauge(f"{name}.last_recovery_from_delta_slots").set(from_delta)
        m.gauge(f"{name}.last_recovery_seconds").set(
            self.last_recovery_seconds)
        m.histogram(f"span.{name}.recovery").record(
            self.last_recovery_seconds)
        self._m_bridge.mark_reset(psync=self.psyncs, op=self.ops)

    def _recheck_overflow(self):
        """Subclass hook: run the facade's one-shot overflow check."""
        self._check_overflow()

    def _post_recovery_overflow(self):
        """Recovery epilogue shared by EVERY recovery path (full, hybrid,
        elastic): the rebuild recomputed ``state.overflow`` from the
        rebuilt index (``recover_impl``), so the one-shot warning must be
        re-armed in the same breath -- a genuine post-recovery overflow
        warns again, a spurious pre-crash latch is gone, and a rebuild
        that still overflows warns immediately on the FRESH latch."""
        self._overflow_warned = False
        self._recheck_overflow()


class DurableMap(MetricsMixin):
    """Object API over the engine (single-controller usage).

    >>> m = DurableMap(SetSpec(capacity=1024, mode="soft", backend="bucket"))
    >>> m.insert([1, 2], [10, 20])
    >>> m.contains([1, 3])          # -> [True, False]
    >>> m.crash_and_recover()       # volatile index lost + rebuilt
    """

    def __init__(self, spec: Optional[SetSpec] = None, metrics=None,
                 metrics_name: str = "map", **spec_kwargs):
        if spec is None:
            spec = SetSpec(**spec_kwargs)
        elif spec_kwargs:
            spec = dataclasses.replace(spec, **spec_kwargs)
        get_backend(spec.backend)        # fail fast on unknown backends
        self.spec = spec
        self.state = make_state(spec)
        self.last_recovery_hist = None   # i32[5] stage histogram, post-recover
        self.last_recovery_seconds = None
        self._overflow_warned = False
        self._m_name = metrics_name
        if metrics is not None:
            self.attach_metrics(metrics, name=metrics_name)

    @staticmethod
    def _i32(x) -> jax.Array:
        return jnp.asarray(x, jnp.int32)

    @property
    def overflowed(self) -> bool:
        """True once the index overflow latch fired: node-pool exhaustion, a
        probe chain past ``max_probe``, or a bucket-backend stash spill past
        ``stash_size``.  Data may be unreachable from that point on --
        detectable, never silent (DESIGN.md §5)."""
        with span("registry.sync.overflow"):
            return bool(self.state.overflow)

    def _check_overflow(self):
        """One-shot warning when a mutating op latches ``state.overflow``
        instead of silently degrading lookups."""
        if not self._overflow_warned and self.overflowed:
            self._overflow_warned = True
            warn_structure(
                f"{type(self).__name__} index overflow latched "
                f"(capacity/probe/stash exhausted for spec={self.spec}); "
                "subsequent lookups may miss live keys -- grow capacity, "
                "stash_size, or shard the map", stacklevel=4)

    def insert(self, keys, values=None):
        keys = self._i32(keys)
        values = keys if values is None else self._i32(values)
        self.state, ok = insert(self.state, keys, values, spec=self.spec)
        self._check_overflow()
        return ok

    def remove(self, keys):
        self.state, ok = remove(self.state, self._i32(keys), spec=self.spec)
        return ok

    def contains(self, keys):
        self.state, ok = contains(self.state, self._i32(keys), spec=self.spec)
        return ok

    def get(self, keys, default: int = 0):
        """Values for present keys, ``default`` otherwise."""
        self.state, vals, _ = get(self.state, self._i32(keys),
                                  spec=self.spec, default=default)
        return vals

    def apply(self, ops, keys, values=None):
        """Mixed contains/insert/remove batch; see :func:`apply_batch`."""
        keys = self._i32(keys)
        values = keys if values is None else self._i32(values)
        self.state, res = apply_batch(self.state, self._i32(ops), keys,
                                      values, spec=self.spec)
        self._check_overflow()
        return res

    def crash_and_recover(self, u=None):
        if u is None:
            u = jnp.zeros_like(self.state.cur, jnp.float32)
        self._metrics_pre_recovery()     # device counters are about to reset
        t0 = time.perf_counter()
        self.state, hist = crash_and_recover(self.state, u, spec=self.spec)
        self.last_recovery_hist = np.asarray(hist)
        jax.block_until_ready(self.state.keys)    # honest recovery timing
        self.last_recovery_seconds = time.perf_counter() - t0
        self._metrics_post_recovery(scanned_slots=self.spec.capacity)
        self._post_recovery_overflow()   # latch recomputed; warning re-armed
        return self

    # --- snapshot + delta-log hybrid recovery (DESIGN.md §11) -----------

    _SNAP_FIELDS = ("keys", "values", "cur", "stamp", "bkeys", "bids",
                    "skeys", "sids", "stash_n", "size", "overflow")

    @property
    def supports_hybrid(self) -> bool:
        return supports_hybrid_recovery(self.spec)

    def snapshot_capture(self) -> dict:
        """Cheap synchronous phase: host-copy the durable planes at a
        dispatch boundary and open a new stamp generation.  Every commit
        from here on stamps ``> W``, so the op stream IS the delta log on
        top of this capture.  Zero psyncs: every plane copied is already
        durable (``cur == flushed`` at each dispatch boundary -- commits
        move both in one scatter), so this is a pure read of NVM."""
        w = int(self.state.epoch)
        cap = {
            "watermark": w,
            "raw_stage": np.asarray(self.state.flushed),
            "keys": np.asarray(self.state.keys),
            "values": np.asarray(self.state.values),
            "stamp": np.asarray(self.state.stamp),
        }
        self.state = self.state._replace(epoch=jnp.asarray(w + 1, jnp.int32))
        return cap

    def snapshot_build(self, cap: dict):
        """Expensive asynchronous phase (background-thread safe: a pure
        function of the captured copies): canonicalize the capture by
        running the normal ``recover`` on it, so the stored snapshot is
        exactly the full-rebuild state at watermark W and hybrid recovery
        can patch it in O(delta).  Returns (planes, meta) for the store."""
        st, hist = recover(jnp.asarray(cap["raw_stage"]),
                           jnp.asarray(cap["keys"]),
                           jnp.asarray(cap["values"]),
                           jnp.asarray(cap["stamp"]), spec=self.spec)
        jax.block_until_ready(st.keys)
        planes = {f: np.asarray(getattr(st, f)) for f in self._SNAP_FIELDS}
        planes["raw_stage"] = cap["raw_stage"]
        meta = {"kind": "map", "watermark": cap["watermark"],
                "hist": np.asarray(hist).tolist()}
        return planes, meta

    def _snapshot_state(self, planes: dict) -> SetState:
        """Reconstruct the canonical snapshot state from stored planes
        (the probe ``table`` is all-EMPTY for hybrid-capable backends, so
        ``make_state`` provides it; counters restart at zero exactly as
        full recovery's do)."""
        cur = jnp.asarray(planes["cur"])
        return make_state(self.spec)._replace(
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

    def hybrid_crash_and_recover(self, planes: dict, meta: dict, u=None):
        """Crash (losing the volatile index) and recover from the stored
        snapshot + the stamp delta instead of the full pool: O(delta)
        classification and index patch, bit-identical to
        ``crash_and_recover`` under the same adversary ``u``.  Recovery
        psyncs: exactly 0, as always."""
        if u is None:
            u = jnp.zeros_like(self.state.cur, jnp.float32)
        n = self.spec.capacity
        w = int(meta["watermark"])
        self._metrics_pre_recovery()
        t0 = time.perf_counter()
        crashed = DS.crash(self.state, jnp.asarray(u))
        stamp_h = np.asarray(crashed[3])
        delta = np.flatnonzero(stamp_h > w).astype(np.int32)
        delta_idx = pad_delta(delta, n)
        ovf = bool(np.any(planes["overflow"]))
        snap = self._snapshot_state(planes)
        self.state = hybrid_recover(snap, *crashed,
                                    jnp.asarray(delta_idx), spec=self.spec,
                                    snap_overflowed=ovf)
        # Exact O(delta) stage-histogram correction: the canonical
        # snapshot collapsed DELETED slots to FREE, so the stored
        # capture-time raw stages reconstruct what a full scan over the
        # crash planes would have counted.
        crash_stage = np.asarray(crashed[0])
        hist = (np.asarray(meta["hist"], np.int64)
                - np.bincount(np.clip(planes["raw_stage"][delta], 0, 4),
                              minlength=5)
                + np.bincount(np.clip(crash_stage[delta], 0, 4),
                              minlength=5))
        self.last_recovery_hist = hist.astype(np.int32)
        jax.block_until_ready(self.state.keys)
        self.last_recovery_seconds = time.perf_counter() - t0
        self._metrics_post_recovery(
            scanned_slots=int(delta.size), from_snapshot=n - int(delta.size),
            from_delta=int(delta.size),
            patch=hybrid_patch_path(self.spec, delta_idx.size, ovf))
        self._post_recovery_overflow()
        return self

    @property
    def psyncs(self):
        return int(self.state.n_psync)

    @property
    def ops(self):
        return int(self.state.n_ops)

    def __len__(self):
        return int(self.state.size)

    def __repr__(self):
        return (f"DurableMap(size={len(self)}, psyncs={self.psyncs}, "
                f"spec={self.spec})")


class DurableSet(DurableMap):
    """Deprecated legacy surface: use ``DurableMap(SetSpec(...))``.

    The old ``index=`` kwarg maps 1:1 onto backend names.
    """

    def __init__(self, capacity: int, mode: str = "soft",
                 index: str = "probe"):
        warnings.warn("DurableSet is deprecated; use "
                      "DurableMap(SetSpec(capacity=..., mode=..., "
                      "backend=...))", DeprecationWarning, stacklevel=2)
        super().__init__(SetSpec(capacity=capacity, mode=mode, backend=index))
        self.mode, self.index = mode, index
