"""End-to-end crash adversary for the durable request/completion spine.

The serving spine (repro.launch.serve --queue, DESIGN.md §7) composes
three durable structures -- request DurableQueue, response DurableQueue,
completion DurableMap registry -- in the order

  1. durable ack       req_q.enqueue(ids)          (psync per request)
  2. volatile peek     req_q.peek(b)               (zero psync)
  3. process           pure compute
  4. response enqueue  resp_q.enqueue(ids)
  5. registry insert   registry.insert(ids, vals)
  6. dequeue COMMIT    req_q.dequeue(b)

The dequeue becomes durable only after the completion is recorded, so a
crash after ANY step loses no acknowledged request: it is either still
live in the recovered request queue (re-served; the registry dedups the
redelivery) or already registered.  This battery crashes at every step
boundary, recovers all three structures, runs the redelivery drain, and
asserts exactly-once completion.
"""
import numpy as np
import pytest

from repro.core import (DurableMap, DurableQueue, QueueSpec, SetSpec,
                        ShardedDurableMap)

STEPS = ("after_ack", "after_peek", "after_resp_enqueue",
         "after_registry_insert", "after_dequeue_commit")


def _process(ids):
    """Stand-in for serving a request: the recorded completion value."""
    return (ids * 2 + 1).astype(np.int32)


def _make_spine(capacity=16, backend="probe"):
    qspec = QueueSpec(capacity=capacity)
    return (DurableQueue(qspec), DurableQueue(qspec),
            DurableMap(SetSpec(capacity=4 * capacity, backend=backend)))


def _run_until(req_q, resp_q, registry, ids, crash_after):
    """Drive one batch through the spine, stopping after ``crash_after``."""
    acked = np.asarray(req_q.enqueue(ids))
    assert acked.all(), "admission queue full"
    if crash_after == "after_ack":
        return
    served, ok = req_q.peek(len(ids))
    np.testing.assert_array_equal(served[ok], ids)
    if crash_after == "after_peek":
        return
    resp_q.enqueue(served[ok])
    if crash_after == "after_resp_enqueue":
        return
    registry.insert(ids, _process(ids))
    if crash_after == "after_registry_insert":
        return
    _, committed = req_q.dequeue(len(ids))
    assert committed.all()
    assert crash_after == "after_dequeue_commit"


def _crash_all(req_q, resp_q, registry, rng):
    n = req_q.spec.capacity
    req_q.crash_and_recover(u=rng.random(n).astype(np.float32))
    resp_q.crash_and_recover(u=rng.random(n).astype(np.float32))
    registry.crash_and_recover()
    assert req_q.psyncs == 0 and resp_q.psyncs == 0, \
        "recovery must issue no psync"


def _drain(req_q, resp_q, registry):
    """Redelivery loop a recovered server runs: re-serve every request
    still live in the request queue, skipping (deduping) the ones the
    registry already shows completed, then commit their dequeues."""
    while len(req_q) > 0:
        live, ok = req_q.peek(req_q.spec.capacity)
        live = live[np.asarray(ok)]
        fresh = live[~np.array(registry.contains(live), bool)]
        if fresh.size:
            resp_q.enqueue(fresh)
            registry.insert(fresh, _process(fresh))
        _, committed = req_q.dequeue(len(live))
        assert np.asarray(committed).all()


@pytest.mark.parametrize("crash_after", STEPS)
def test_no_acked_request_lost_no_completion_duplicated(crash_after):
    """Crash at every spine step boundary under the per-slot eviction
    adversary: after recovery + drain, every acknowledged request is
    registered EXACTLY once and the request queue is empty."""
    rng = np.random.default_rng(STEPS.index(crash_after))
    req_q, resp_q, registry = _make_spine()
    ids = np.arange(100, 108, dtype=np.int32)
    _run_until(req_q, resp_q, registry, ids, crash_after)
    _crash_all(req_q, resp_q, registry, rng)
    _drain(req_q, resp_q, registry)
    done = np.array(registry.contains(ids))
    assert done.all(), f"lost acked requests {ids[~done]} ({crash_after})"
    assert len(registry) == len(ids), "completion duplicated in registry"
    assert len(req_q) == 0 and not req_q.overflowed
    # at-least-once on the response queue: every id present (duplicates
    # allowed only for the crash-between-resp-and-registry window)
    resp, ok = resp_q.peek(resp_q.spec.capacity)
    assert set(ids.tolist()) <= set(resp[np.asarray(ok)].tolist())


@pytest.mark.parametrize("backend", ("probe", "scan", "bucket"))
def test_multi_wave_spine_with_interleaved_crashes(backend):
    """Several waves through a small ring (forcing ticket wraparound in
    spine usage) with a crash at a random step boundary each wave: the
    registry ends with every acked id exactly once, monotone across
    waves."""
    rng = np.random.default_rng(42)
    req_q, resp_q, registry = _make_spine(capacity=8, backend=backend)
    all_ids = []
    for wave in range(6):
        ids = np.arange(200 + 8 * wave, 200 + 8 * wave + 4, dtype=np.int32)
        all_ids += ids.tolist()
        _run_until(req_q, resp_q, registry, ids,
                   STEPS[rng.integers(0, len(STEPS))])
        _crash_all(req_q, resp_q, registry, rng)
        _drain(req_q, resp_q, registry)
        done = np.array(registry.contains(np.asarray(all_ids, np.int32)))
        assert done.all(), f"wave {wave} lost {np.asarray(all_ids)[~done]}"
        assert len(registry) == len(all_ids)
        # drain the response queue like a completion notifier would; its
        # set must cover this wave's ids
        got, ok = resp_q.dequeue(8)
        assert set(ids.tolist()) <= set(got[np.asarray(ok)].tolist())
        while len(resp_q):
            resp_q.dequeue(8)
    assert not req_q.overflowed and not resp_q.overflowed


def test_spine_psync_bound():
    """Crash-free spine pass costs exactly 4 psyncs per request (ack +
    response + registry insert + dequeue commit) -- the SOFT per-op bound
    composed across the three structures, nothing hidden."""
    req_q, resp_q, registry = _make_spine()
    ids = np.arange(8, dtype=np.int32)
    _run_until(req_q, resp_q, registry, ids, "after_dequeue_commit")
    total = req_q.psyncs + resp_q.psyncs + registry.psyncs
    assert total == 4 * len(ids), (req_q.psyncs, resp_q.psyncs,
                                   registry.psyncs)


def test_pipelined_spine_exactly_once_and_psync_bound():
    """The ``serve.py --pipeline`` wave loop (DESIGN.md §6): wave k+1's
    durable ack enqueues before wave k is recorded, and each wave's
    pipelined registry insert is flushed durable BEFORE that wave's
    dequeue commit.  Exactly-once completion and the exact 4
    psyncs/request bill survive pipelining unchanged."""
    qspec = QueueSpec(capacity=32)
    req_q, resp_q = DurableQueue(qspec), DurableQueue(qspec)
    registry = ShardedDurableMap(SetSpec(capacity=128), n_shards=4,
                                 pipeline_depth=2)
    ids = np.arange(300, 316, dtype=np.int32)
    waves = np.array_split(ids, 4)
    assert np.asarray(req_q.enqueue(waves[0])).all()
    for k, wave in enumerate(waves):
        served, ok = req_q.peek(len(wave))          # volatile, zero psync
        np.testing.assert_array_equal(served[np.asarray(ok)], wave)
        if k + 1 < len(waves):   # ack wave k+1 before wave k is recorded
            assert np.asarray(req_q.enqueue(waves[k + 1])).all()
        resp_q.enqueue(wave)
        registry.insert(wave, _process(wave))       # staged, lazy
        registry.pipeline_flush()   # durable BEFORE the dequeue commit
        _, committed = req_q.dequeue(len(wave))
        assert np.asarray(committed).all()
    total = req_q.psyncs + resp_q.psyncs + registry.psyncs
    assert total == 4 * len(ids), (req_q.psyncs, resp_q.psyncs,
                                   registry.psyncs)
    assert len(registry) == len(ids) and len(req_q) == 0
    assert np.array(registry.contains(ids)).all()


def test_pipelined_spine_crash_before_flush_loses_nothing():
    """Crash with a wave's registry insert still STAGED (after response
    enqueue, before flush + dequeue commit): the staged insert is
    abandoned psync-free, the wave is still live in the recovered request
    queue -- because its dequeue never committed -- and the redelivery
    drain completes it exactly once."""
    rng = np.random.default_rng(7)
    qspec = QueueSpec(capacity=16)
    req_q, resp_q = DurableQueue(qspec), DurableQueue(qspec)
    registry = ShardedDurableMap(SetSpec(capacity=128), n_shards=4,
                                 pipeline_depth=2)
    done = np.arange(400, 404, dtype=np.int32)   # wave 0 completes fully
    assert np.asarray(req_q.enqueue(done)).all()
    resp_q.enqueue(done)
    registry.insert(done, _process(done))
    registry.pipeline_flush()
    _, committed = req_q.dequeue(len(done))
    assert np.asarray(committed).all()
    live = np.arange(404, 408, dtype=np.int32)   # wave 1 crashes mid-wave
    assert np.asarray(req_q.enqueue(live)).all()
    resp_q.enqueue(live)
    h = registry.insert(live, _process(live))    # staged, NOT yet durable
    n = req_q.spec.capacity
    req_q.crash_and_recover(u=rng.random(n).astype(np.float32))
    resp_q.crash_and_recover(u=rng.random(n).astype(np.float32))
    registry.crash_and_recover()
    assert h.abandoned and registry.pipeline_abandoned == 1
    assert len(req_q) == len(live), "uncommitted wave must stay live"
    _drain(req_q, resp_q, registry)
    all_ids = np.concatenate([done, live])
    assert np.array(registry.contains(all_ids)).all()
    assert len(registry) == len(all_ids) and len(req_q) == 0
