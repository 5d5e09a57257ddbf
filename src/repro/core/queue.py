"""Durable lock-free MPMC ring queue on the shared stage machine.

The paper's durable-set recipe is structure-agnostic: a node's durable
lifecycle is the monotone FREE -> INVALID -> PAYLOAD -> VALID -> DELETED
machine of :mod:`repro.core.nvm`, all writes to one cache line, recovery a
pure classification of persisted stages.  *Durable Queues: The Second
Amendment* (PAPERS.md) shows the same discipline yields a durable FIFO
queue with provably low flush counts; this module is that construction on
the engine's batched lane model (DESIGN.md SS7):

  ring          N = capacity slots (power of two).  Element *tickets* are
                a monotone virtual sequence; ticket t lives in slot
                ``t & (N-1)``, so slot reuse is a fresh stage-machine
                incarnation exactly like the set's ssmem recycling (a slot
                is re-enqueued only after its previous dequeue's psync --
                the ring-distance guard ``ticket < head + N`` implies the
                prior incarnation is flushed-DELETED).
  enqueue       plan/commit (DESIGN.md SS2a): active lanes claim tickets by
                lane rank (the ``table_claim`` conflict-resolution idiom --
                rank r takes ticket tail+r, conflicts impossible because
                distinct tickets hit distinct slots), then ONE scatter per
                state plane commits payload+stage: cur=VALID, flushed=VALID
                (write INVALID -> payload -> makeValid -> psync, collapsed
                like the set's insert commit).  Lanes past the free-space
                budget fail (queue full): result False, ZERO psync.
  dequeue       ranks claim tickets head+r; wins gather the payload and
                commit cur=DELETED, flushed=DELETED in one scatter (mark ->
                psync).  Lanes past ``tail`` fail (queue empty): result
                False, ZERO psync.
  psync         SOFT: exactly 1 per successful enqueue/dequeue -- the
                Cohen et al. lower bound the Fence Complexity paper
                formalizes -- and 0 for failed ops, 0 for reads (peek),
                0 during recovery.  logfree models the link-persist
                baseline at 2 per successful op.
  recovery      head/tail are VOLATILE (rebuilt, never persisted -- the
                queue-level analogue of the set's volatile index).
                :func:`recover` classifies persisted stages with the
                Pallas ``recovery_scan`` kernel and
                reconstructs: live elements = persisted-VALID slots in
                ticket order; head = min live ticket (else one past the
                newest persisted-DELETED ticket); tail = one past the max
                live ticket.  FIFO discipline means live tickets form the
                contiguous range [head, tail); a violated invariant latches
                ``overflow`` -- detectable, never silent.

Tickets are i32: the module supports 2^31 enqueues per state lifetime
(recovery does not reset tickets of surviving elements).

:class:`DurableQueue` mirrors the :class:`DurableMap` facade (psyncs / ops
/ len / overflowed / crash_and_recover), so the serving spine in
:mod:`repro.launch.serve` composes the two behind one idiom.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import durable_set as DS
from repro.core.durable_set import MODES
from repro.core.engine import MetricsMixin, warn_structure
from repro.core.nvm import (FREE, VALID, DELETED, crash_persisted_stage)
from repro.kernels.recovery_scan import ops as rs_ops
from repro.obs.metrics import span


@dataclasses.dataclass(frozen=True)
class QueueSpec:
    """Frozen configuration of a durable queue (hashable => static jit arg).

    capacity    ring slots N (power of two: slot = ticket & (N-1))
    mode        psync discipline: "soft" (1 psync per successful op, the
                bound) | "linkfree" (same count here: the queue has no
                read-side helping) | "logfree" (2 per successful op,
                the link-persist baseline)
    use_pallas  route recovery classification through the Pallas
                ``recovery_scan`` kernel (compiled on the TPU, interpreted
                elsewhere) instead of its jnp reference
    """
    capacity: int
    mode: str = "soft"
    use_pallas: bool = True

    def __post_init__(self):
        c = self.capacity
        if c < 1 or (c & (c - 1)) != 0:
            raise ValueError("capacity must be a power of two (ring slot = "
                             f"ticket & (N-1)), got {c}")
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")

    def psync_per_success(self) -> int:
        """Explicit psyncs per successful enqueue/dequeue (the mode's whole
        performance story; failed ops always pay zero)."""
        return 2 if self.mode == "logfree" else 1


class QueueState(NamedTuple):
    """Durable ring + volatile cursors + psync accounting.

    ``head``/``tail`` are the volatile FIFO cursors (next dequeue / next
    enqueue ticket); a crash discards them and recovery reconstructs both
    from persisted stages alone -- they are the queue's "volatile index".
    """
    # --- durable area; vals/tickets persist once stage >= PAYLOAD
    vals: jax.Array      # i32[N] element payloads
    tickets: jax.Array   # i32[N] slot incarnation ticket (== virtual seq no)
    cur: jax.Array       # i32[N] volatile lifecycle stage
    flushed: jax.Array   # i32[N] stage covered by the last explicit psync
    stamp: jax.Array     # i32[N] epoch of the last durable commit per slot
    #                      (rides the commit scatter: zero extra psyncs;
    #                      DESIGN.md §11 snapshot + delta-log recovery)
    # --- volatile cursors (never persisted)
    head: jax.Array      # i32[] next dequeue ticket
    tail: jax.Array      # i32[] next enqueue ticket
    # --- accounting (COUNTER_DTYPE: i64[] under x64, saturating i32[] else)
    n_psync: jax.Array   # explicit flush+fence count
    n_ops: jax.Array     # attempted operations (failed ones included)
    overflow: jax.Array  # bool[] full-enqueue-rejected / invariant latch
    epoch: jax.Array     # i32[] VOLATILE generation counter (snapshotter
    #                      watermark discipline, same as SetState.epoch)


def make_state(spec: QueueSpec) -> QueueState:
    n = spec.capacity
    return QueueState(
        vals=jnp.zeros((n,), jnp.int32),
        tickets=jnp.zeros((n,), jnp.int32),
        cur=jnp.zeros((n,), jnp.int32),
        flushed=jnp.zeros((n,), jnp.int32),
        stamp=jnp.zeros((n,), jnp.int32),
        head=jnp.zeros((), jnp.int32),
        tail=jnp.zeros((), jnp.int32),
        n_psync=jnp.zeros((), DS.COUNTER_DTYPE),
        n_ops=jnp.zeros((), DS.COUNTER_DTYPE),
        overflow=jnp.zeros((), jnp.bool_),
        epoch=jnp.ones((), jnp.int32),   # stamp==0 means "never committed"
    )


def size(state: QueueState) -> jax.Array:
    """Live element count (tail - head)."""
    return state.tail - state.head


# ---------------------------------------------------------------------------
# Plan/commit hot path.  Both ops share the rank-claim plan: active lanes
# take consecutive tickets by lane rank (lane priority IS the linearization
# order, as everywhere in DESIGN.md SS2), wins are the ranks inside the
# cursor budget, and the commit is one scatter per touched state plane.
# ---------------------------------------------------------------------------


def _rank_claim(active: jax.Array, base: jax.Array, budget: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """(ticket per lane, win mask): active lane of rank r claims ticket
    base+r and wins iff r < budget."""
    rank = jnp.cumsum(active.astype(jnp.int32)) - 1
    win = active & (rank < budget)
    return base + rank, win


def enqueue_impl(state: QueueState, vals: jax.Array, *, spec: QueueSpec,
                 active: Optional[jax.Array] = None
                 ) -> Tuple[QueueState, jax.Array, jax.Array]:
    """Unjitted batched enqueue body: (state, ok[B], ticket-or-minus-1[B]).

    Winning lanes' slots held a flushed-DELETED (or never-used FREE)
    incarnation -- the ``rank < N - size`` budget guarantees it -- so the
    commit may recycle them directly: payload + ticket + cur/flushed=VALID
    land in one scatter per plane, modeling write-INVALID -> payload ->
    makeValid -> psync with the per-op psync counted exactly."""
    b = vals.shape[0]
    if active is None:
        active = jnp.ones((b,), jnp.bool_)
    n = spec.capacity
    ticket, win = _rank_claim(active, state.tail,
                              jnp.int32(n) - size(state))
    slot = ticket & (n - 1)
    sidx = jnp.where(win, slot, n)                # OOB scatter => dropped
    count = jnp.sum(win.astype(jnp.int32))
    full = (active & ~win).any()
    return QueueState(
        vals=state.vals.at[sidx].set(vals, mode="drop"),
        tickets=state.tickets.at[sidx].set(ticket, mode="drop"),
        cur=state.cur.at[sidx].set(VALID, mode="drop"),
        flushed=state.flushed.at[sidx].set(VALID, mode="drop"),
        stamp=state.stamp.at[sidx].set(
            jnp.broadcast_to(state.epoch, sidx.shape), mode="drop"),
        head=state.head,
        tail=state.tail + count,
        n_psync=DS._bump(state.n_psync, count * spec.psync_per_success()),
        n_ops=DS._bump(state.n_ops, jnp.sum(active.astype(jnp.int32))),
        overflow=state.overflow | full,
        epoch=state.epoch,
    ), win, jnp.where(win, ticket, -1)


def dequeue_impl(state: QueueState, want: jax.Array, *, spec: QueueSpec,
                 default: int = 0
                 ) -> Tuple[QueueState, jax.Array, jax.Array, jax.Array]:
    """Unjitted batched dequeue body: lanes with ``want`` pop in lane
    order.  Returns (state, value-or-default[B], ok[B], ticket-or-minus-1).

    The commit is mark -> psync collapsed: cur=DELETED, flushed=DELETED in
    one scatter.  Empty-queue lanes fail with zero psync."""
    n = spec.capacity
    ticket, win = _rank_claim(want, state.head, size(state))
    slot = ticket & (n - 1)
    got = jnp.where(win, state.vals[jnp.clip(slot, 0, n - 1)],
                    jnp.int32(default))
    sidx = jnp.where(win, slot, n)
    count = jnp.sum(win.astype(jnp.int32))
    return QueueState(
        vals=state.vals, tickets=state.tickets,
        cur=state.cur.at[sidx].set(DELETED, mode="drop"),
        flushed=state.flushed.at[sidx].set(DELETED, mode="drop"),
        stamp=state.stamp.at[sidx].set(
            jnp.broadcast_to(state.epoch, sidx.shape), mode="drop"),
        head=state.head + count,
        tail=state.tail,
        n_psync=DS._bump(state.n_psync, count * spec.psync_per_success()),
        n_ops=DS._bump(state.n_ops, jnp.sum(want.astype(jnp.int32))),
        overflow=state.overflow,
        epoch=state.epoch,
    ), got, win, jnp.where(win, ticket, -1)


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def enqueue(state: QueueState, vals: jax.Array, *, spec: QueueSpec
            ) -> Tuple[QueueState, jax.Array, jax.Array]:
    """Batched durable enqueue: (state, ok[B], ticket[B])."""
    return enqueue_impl(state, vals, spec=spec)


@functools.partial(jax.jit, static_argnames=("spec", "default"),
                   donate_argnums=(0,))
def dequeue(state: QueueState, want: jax.Array, *, spec: QueueSpec,
            default: int = 0
            ) -> Tuple[QueueState, jax.Array, jax.Array, jax.Array]:
    """Batched durable dequeue: (state, values[B], ok[B], ticket[B])."""
    return dequeue_impl(state, want, spec=spec, default=default)


@functools.partial(jax.jit, static_argnames=("spec", "default"))
def peek(state: QueueState, want: jax.Array, *, spec: QueueSpec,
         default: int = 0) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Volatile read of the head batch WITHOUT consuming it: (values[B],
    ok[B], ticket[B]).  Pure -- no state change, no psync, not an op (the
    SOFT wait-free read bound; the serving spine peeks, processes, records
    the completion durably, and only then commits the dequeue)."""
    n = spec.capacity
    ticket, win = _rank_claim(want, state.head, size(state))
    slot = ticket & (n - 1)
    got = jnp.where(win, state.vals[jnp.clip(slot, 0, n - 1)],
                    jnp.int32(default))
    return got, win, jnp.where(win, ticket, -1)


# ---------------------------------------------------------------------------
# Crash + recovery
# ---------------------------------------------------------------------------


def crash(state: QueueState, u: jax.Array
          ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Power failure: head/tail (the volatile cursors) are LOST.  Returns
    only what NVM holds -- per-slot persisted stage, ticket/value payloads,
    and the epoch stamp plane (each stamp write rode a psync'd commit);
    ``u`` in [0, 1) per slot drives the eviction adversary."""
    persisted = crash_persisted_stage(state.cur, state.flushed, u)
    return persisted, state.tickets, state.vals, state.stamp


def recover_impl(persisted: jax.Array, tickets: jax.Array, vals: jax.Array,
                 stamp: Optional[jax.Array] = None,
                 *, spec: QueueSpec) -> Tuple[QueueState, jax.Array]:
    """Unjitted recovery body (pure jnp reductions => vmappable, e.g. over
    a future stacked-queue axis).  Rebuilds head/tail from persisted
    stages alone:

      live    persisted == VALID  (enqueue completed, dequeue not durable)
      head    min live ticket; with no live element, one past the newest
              persisted-DELETED ticket (all those dequeues completed)
      tail    one past the max live ticket (else == head)

    FIFO discipline (dequeues retire tickets in order; batched commits are
    atomic at the dispatch boundary) makes live tickets exactly the range
    [head, tail); a hole would mean a lost element, so the invariant
    violation latches ``overflow`` instead of passing silently.  No psync
    is ever issued: payloads are already durable."""
    member, hist = rs_ops.recovery_scan(persisted, use_pallas=spec.use_pallas)
    deleted = persisted == DELETED
    any_m = member.any()
    big = jnp.int32(np.iinfo(np.int32).max)
    min_live = jnp.min(jnp.where(member, tickets, big))
    max_live = jnp.max(jnp.where(member, tickets, -big))
    max_del = jnp.max(jnp.where(deleted, tickets, -1))
    head = jnp.where(any_m, min_live, max_del + 1)
    tail = jnp.where(any_m, max_live + 1, head)
    n_live = jnp.sum(member.astype(jnp.int32))
    cur = jnp.where(member, VALID, FREE)
    if stamp is None:
        stamp = jnp.zeros_like(tickets)
        epoch = jnp.ones((), jnp.int32)
    else:
        # Recovery never writes NVM: stamps survive verbatim; the next
        # generation starts strictly above every durable stamp.
        epoch = jnp.maximum(jnp.max(stamp), 0) + 1
    state = QueueState(
        vals=jnp.where(member, vals, 0),
        tickets=jnp.where(member, tickets, 0),
        cur=cur, flushed=cur, stamp=stamp,
        head=head, tail=tail,
        n_psync=jnp.zeros((), DS.COUNTER_DTYPE),
        n_ops=jnp.zeros((), DS.COUNTER_DTYPE),
        overflow=(tail - head) != n_live,     # FIFO-hole invariant latch
        epoch=epoch,
    )
    return state, hist


@functools.partial(jax.jit, static_argnames=("spec",))
def recover(persisted: jax.Array, tickets: jax.Array, vals: jax.Array,
            stamp: Optional[jax.Array] = None, *,
            spec: QueueSpec) -> Tuple[QueueState, jax.Array]:
    """Jitted recovery: classification via the ``recovery_scan`` kernel +
    head/tail reconstruction.  Returns (state, stage histogram i32[5])."""
    return recover_impl(persisted, tickets, vals, stamp, spec=spec)


def crash_and_recover(state: QueueState, u: jax.Array, *, spec: QueueSpec
                      ) -> Tuple[QueueState, jax.Array]:
    return recover(*crash(state, u), spec=spec)


def hybrid_recover_impl(snap: QueueState, persisted: jax.Array,
                        tickets: jax.Array, vals: jax.Array,
                        stamp: jax.Array, delta_idx: jax.Array,
                        *, spec: QueueSpec) -> QueueState:
    """Unjitted snapshot + delta-log recovery body (DESIGN.md §11).

    ``snap`` is the canonical recovered state at watermark W (its
    ``head``/``tail`` are the capture-time cursors); the other planes are
    crash-time NVM contents and ``delta_idx`` i32[D] lists the slots with
    ``stamp > W`` (padded with ``capacity``).  Classification runs over the
    gathered delta only; cursor reconstruction reuses the full-recovery
    formulas on the merged planes, with one subtlety: the newest durably
    retired ticket is either in the delta or was already retired at
    capture, where FIFO contiguity pins it to ``snap.head - 1`` (every
    ticket below the head cursor is durably dequeued, every ticket at or
    above it is not).  Bit-identical to ``recover`` on the same crash
    planes; no psync is ever issued."""
    n = spec.capacity
    valid = delta_idx < n
    gi = jnp.where(valid, delta_idx, 0)
    d_per = jnp.where(valid, persisted[gi], 0)
    member_d, _ = rs_ops.recovery_scan(d_per, use_pallas=spec.use_pallas)
    member_d = member_d & valid

    scat = jnp.where(valid, delta_idx, n)           # OOB scatter => dropped
    tickets_d = jnp.where(valid, tickets[gi], 0)
    tickets2 = snap.tickets.at[scat].set(
        jnp.where(member_d, tickets_d, 0), mode="drop")
    vals2 = snap.vals.at[scat].set(
        jnp.where(member_d, vals[gi], 0), mode="drop")
    cur2 = snap.cur.at[scat].set(
        jnp.where(member_d, VALID, FREE), mode="drop")
    stamp2 = snap.stamp.at[scat].set(stamp[gi], mode="drop")

    member2 = cur2 == VALID
    any_m = member2.any()
    big = jnp.int32(np.iinfo(np.int32).max)
    min_live = jnp.min(jnp.where(member2, tickets2, big))
    max_live = jnp.max(jnp.where(member2, tickets2, -big))
    max_del_delta = jnp.max(jnp.where(valid & (d_per == DELETED),
                                      tickets_d, -1))
    max_del = jnp.maximum(snap.head - 1, max_del_delta)
    head = jnp.where(any_m, min_live, max_del + 1)
    tail = jnp.where(any_m, max_live + 1, head)
    n_live = jnp.sum(member2.astype(jnp.int32))
    return snap._replace(
        vals=vals2, tickets=tickets2, cur=cur2, flushed=cur2, stamp=stamp2,
        head=head, tail=tail,
        n_psync=jnp.zeros((), DS.COUNTER_DTYPE),
        n_ops=jnp.zeros((), DS.COUNTER_DTYPE),
        overflow=(tail - head) != n_live,
        epoch=jnp.maximum(jnp.max(stamp2), 0) + 1,
    )


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def hybrid_recover(snap: QueueState, persisted: jax.Array,
                   tickets: jax.Array, vals: jax.Array, stamp: jax.Array,
                   delta_idx: jax.Array, *, spec: QueueSpec) -> QueueState:
    """Jitted snapshot + delta-log recovery, bit-identical to ``recover``
    on the same crash planes (pinned by tests/test_snapshot.py)."""
    return hybrid_recover_impl(snap, persisted, tickets, vals, stamp,
                               delta_idx, spec=spec)


# ---------------------------------------------------------------------------
# OO facade (mirrors DurableMap)
# ---------------------------------------------------------------------------


def _read_vals_ok(vals: jax.Array, ok: jax.Array):
    """The host reads of a dequeue or peek, one sync span each."""
    with span("queue.sync.vals"):
        vals = np.asarray(vals)
    with span("queue.sync.ok"):
        ok = np.asarray(ok)
    return vals, ok


class DurableQueue(MetricsMixin):
    """Object API over the durable ring queue (single-controller usage).

    >>> q = DurableQueue(QueueSpec(capacity=1024))
    >>> q.enqueue([7, 8, 9])          # -> [True, True, True], 3 psyncs
    >>> q.crash_and_recover()         # head/tail lost + rebuilt
    >>> q.dequeue(2)                  # -> ([7, 8], [True, True])

    Pass ``metrics=MetricsRegistry(...)`` to expose psync/op totals,
    size, the overflow latch, and recovery spans through the registry's
    ``snapshot()`` (DESIGN.md §10); ``metrics_name`` namespaces the
    entries (default "queue").
    """

    def __init__(self, spec: Optional[QueueSpec] = None, metrics=None,
                 metrics_name: str = "queue", **spec_kwargs):
        if spec is None:
            spec = QueueSpec(**spec_kwargs)
        elif spec_kwargs:
            spec = dataclasses.replace(spec, **spec_kwargs)
        self.spec = spec
        self.state = make_state(spec)
        self.last_recovery_hist = None    # i32[5] stage histogram
        self.last_recovery_seconds = None
        self.last_tickets = None          # tickets of the last enqueue batch
        self._overflow_warned = False
        self._m_name = metrics_name
        if metrics is not None:
            self.attach_metrics(metrics, name=metrics_name)

    @property
    def overflowed(self) -> bool:
        """True once the latch fired: an enqueue was rejected on a full
        ring, or recovery found a FIFO-range hole.  Detectable, never
        silent (the queue analogue of ``DurableMap.overflowed``)."""
        with span("queue.sync.overflow"):
            return bool(self.state.overflow)

    def _check_overflow(self):
        if not self._overflow_warned and self.overflowed:
            self._overflow_warned = True
            warn_structure(
                f"DurableQueue full: an enqueue was rejected (or recovery "
                f"found a FIFO hole) for spec={self.spec}; rejected lanes "
                "returned False -- drain faster or grow capacity",
                stacklevel=4)

    def enqueue(self, vals):
        vals = jnp.asarray(vals, jnp.int32)
        with span("queue.enqueue"):
            self.state, ok, tickets = enqueue(self.state, vals,
                                              spec=self.spec)
        with span("queue.sync.tickets"):
            self.last_tickets = np.asarray(tickets)
        self._check_overflow()
        return ok

    def dequeue(self, n: int, default: int = 0):
        """Pop up to ``n`` elements; returns (values, ok) np arrays."""
        want = jnp.ones((n,), jnp.bool_)
        with span("queue.dequeue"):
            self.state, vals, ok, _ = dequeue(self.state, want,
                                              spec=self.spec,
                                              default=default)
        return _read_vals_ok(vals, ok)

    def peek(self, n: int, default: int = 0):
        """Read up to ``n`` head elements without consuming (no psync)."""
        want = jnp.ones((n,), jnp.bool_)
        vals, ok, _ = peek(self.state, want, spec=self.spec, default=default)
        return _read_vals_ok(vals, ok)

    def crash_and_recover(self, u=None):
        if u is None:
            u = jnp.zeros_like(self.state.cur, jnp.float32)
        self._metrics_pre_recovery()      # counters are about to reset
        t0 = time.perf_counter()
        self.state, hist = crash_and_recover(self.state, jnp.asarray(u),
                                             spec=self.spec)
        self.last_recovery_hist = np.asarray(hist)
        jax.block_until_ready(self.state.vals)
        self.last_recovery_seconds = time.perf_counter() - t0
        self._metrics_post_recovery(scanned_slots=self.spec.capacity)
        self._post_recovery_overflow()    # latch recomputed; warning re-armed
        return self

    # --- snapshot + delta-log hybrid recovery (DESIGN.md §11) -----------

    _SNAP_FIELDS = ("vals", "tickets", "cur", "stamp", "head", "tail",
                    "overflow")

    supports_hybrid = True    # the ring has no order-dependent index

    def snapshot_capture(self) -> dict:
        """Host-copy the durable planes at a dispatch boundary and open a
        new stamp generation (watermark discipline identical to
        ``DurableMap.snapshot_capture``; zero psyncs -- a pure NVM read)."""
        w = int(self.state.epoch)
        cap = {
            "watermark": w,
            "raw_stage": np.asarray(self.state.flushed),
            "tickets": np.asarray(self.state.tickets),
            "vals": np.asarray(self.state.vals),
            "stamp": np.asarray(self.state.stamp),
        }
        self.state = self.state._replace(epoch=jnp.asarray(w + 1, jnp.int32))
        return cap

    def snapshot_build(self, cap: dict):
        """Canonicalize the capture with the normal ``recover`` (background
        -thread safe); the stored snapshot is the full-rebuild state at the
        watermark, cursors included.  Returns (planes, meta)."""
        st, hist = recover(jnp.asarray(cap["raw_stage"]),
                           jnp.asarray(cap["tickets"]),
                           jnp.asarray(cap["vals"]),
                           jnp.asarray(cap["stamp"]), spec=self.spec)
        jax.block_until_ready(st.vals)
        planes = {f: np.asarray(getattr(st, f)) for f in self._SNAP_FIELDS}
        planes["raw_stage"] = cap["raw_stage"]
        meta = {"kind": "queue", "watermark": cap["watermark"],
                "hist": np.asarray(hist).tolist()}
        return planes, meta

    def _snapshot_state(self, planes: dict) -> QueueState:
        cur = jnp.asarray(planes["cur"])
        return make_state(self.spec)._replace(
            vals=jnp.asarray(planes["vals"]),
            tickets=jnp.asarray(planes["tickets"]),
            cur=cur, flushed=cur,
            stamp=jnp.asarray(planes["stamp"]),
            head=jnp.asarray(planes["head"]),
            tail=jnp.asarray(planes["tail"]),
            overflow=jnp.asarray(planes["overflow"]))

    def hybrid_crash_and_recover(self, planes: dict, meta: dict, u=None):
        """Crash (losing head/tail) and recover from the stored snapshot +
        the stamp delta; bit-identical to ``crash_and_recover`` under the
        same adversary.  Recovery psyncs: exactly 0."""
        from repro.core.engine import pad_delta
        if u is None:
            u = jnp.zeros_like(self.state.cur, jnp.float32)
        n = self.spec.capacity
        w = int(meta["watermark"])
        self._metrics_pre_recovery()
        t0 = time.perf_counter()
        crashed = crash(self.state, jnp.asarray(u))
        delta = np.flatnonzero(np.asarray(crashed[3]) > w).astype(np.int32)
        delta_idx = pad_delta(delta, n)
        snap = self._snapshot_state(planes)
        self.state = hybrid_recover(snap, *crashed,
                                    jnp.asarray(delta_idx), spec=self.spec)
        crash_stage = np.asarray(crashed[0])
        hist = (np.asarray(meta["hist"], np.int64)
                - np.bincount(np.clip(planes["raw_stage"][delta], 0, 4),
                              minlength=5)
                + np.bincount(np.clip(crash_stage[delta], 0, 4),
                              minlength=5))
        self.last_recovery_hist = hist.astype(np.int32)
        jax.block_until_ready(self.state.vals)
        self.last_recovery_seconds = time.perf_counter() - t0
        self._metrics_post_recovery(scanned_slots=int(delta.size),
                                    from_snapshot=n - int(delta.size),
                                    from_delta=int(delta.size))
        self._post_recovery_overflow()
        return self

    @property
    def psyncs(self):
        return int(self.state.n_psync)

    @property
    def ops(self):
        return int(self.state.n_ops)

    def __len__(self):
        return int(size(self.state))

    def __repr__(self):
        return (f"DurableQueue(size={len(self)}, psyncs={self.psyncs}, "
                f"spec={self.spec})")
