"""Spans on the profiler's clock (``repro.obs.span``, DESIGN.md §10).

  1. THE PRIMITIVE -- a span opens a ``jax.profiler.TraceAnnotation``
     under its name, and records into a registry's ``span.<name>``
     histogram only when given one.
  2. THE SERVED PATH -- with ``TraceAnnotation`` replaced by a recorder,
     one sharded registry batch, one spine round and one crash show the
     layer spans by name and nesting, and exactly the ``*.sync.*`` spans
     (one per device-to-host read) that the path makes: one per batch,
     four per spine round; the queue façade's, router v1's and the
     elastic map's reads are sync spans too.
  3. THE SNAPSHOT RESTART -- a snapshot shows its capture, build and
     save; a restart through it shows its restore and the hybrid
     recovery's steps, with exactly three sync spans and no psync; a
     restart with no committed snapshot counts a fallback.
"""
import jax
import numpy as np
import pytest

from repro.core import (DurableQueue, QueueSpec, SetSpec,
                        ShardedDurableMap)
from repro.core.resize import ElasticShardedMap
from repro.core.engine import OP_CONTAINS, OP_INSERT, OP_NOP
from repro.launch import bench_serve
from repro.obs import MetricsRegistry, span


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps every span
    opened as (name, enclosing span or None), in order."""

    def __init__(self):
        self.events, self._stack = [], []

    def __call__(self, name):
        rec = self

        class _Ann:
            def __enter__(self):
                rec.events.append((name, rec._stack[-1] if rec._stack
                                   else None))
                rec._stack.append(name)
                return self

            def __exit__(self, *exc):
                rec._stack.pop()
        return _Ann()

    def names(self):
        return [n for n, _ in self.events]

    def syncs(self):
        return [n for n in self.names() if ".sync." in n]

    def clear(self):
        self.events.clear()


@pytest.fixture
def spans(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    return rec


# ---------------------------------------------------------------------------
# 1. The primitive
# ---------------------------------------------------------------------------


def test_span_annotates_without_a_registry(spans):
    with span("layer.step"):
        with span("layer.sync.what"):
            pass
    assert spans.events == [("layer.step", None),
                            ("layer.sync.what", "layer.step")]


def test_span_records_only_when_given_a_registry(spans):
    m = MetricsRegistry()
    with span("layer.plain"):
        pass
    with span("layer.timed", m):
        pass
    with m.span("layer.method"):
        pass
    hists = m.snapshot()["histograms"]
    assert set(hists) == {"span.layer.timed", "span.layer.method"}
    assert hists["span.layer.timed"]["count"] == 1
    assert hists["span.layer.timed"]["p50"] >= 0
    assert spans.names() == ["layer.plain", "layer.timed", "layer.method"]


def test_timed_span_records_when_the_body_raises(spans):
    m = MetricsRegistry()
    with pytest.raises(KeyError):
        with span("layer.fails", m):
            raise KeyError("x")
    assert m.snapshot()["histograms"]["span.layer.fails"]["count"] == 1
    assert spans.names() == ["layer.fails"]


def test_real_annotation_is_the_default():
    # the unpatched primitive runs with the profiler off
    with span("layer.real"):
        pass


# ---------------------------------------------------------------------------
# 2. The served path
# ---------------------------------------------------------------------------


def _registry():
    return ShardedDurableMap(SetSpec(capacity=1024, backend="probe"),
                             n_shards=4)


def _batch(rng, b=64):
    keys = rng.integers(0, 1 << 20, b).astype(np.int32)
    ops = rng.choice([OP_CONTAINS, OP_INSERT], b).astype(np.int32)
    return ops, keys


def test_registry_batch_spans_and_four_syncs(spans):
    """The name counts the four reads a batch made before its outputs
    were packed into one; the test pins that one read."""
    m = _registry()
    rng = np.random.default_rng(0)
    m.apply(*_batch(rng))                 # compile outside the count
    spans.clear()
    m.apply(*_batch(rng))
    names = spans.names()
    assert names[:3] == ["registry.route", "registry.launch",
                         "registry.force"]
    # results, kept mask, dropped count and overflow latch: one read
    assert spans.syncs() == ["registry.sync.batch"]
    parent = dict(spans.events)
    assert parent["registry.sync.batch"] == "registry.force"
    assert parent["registry.route"] is None


def test_registry_get_reads_values_and_present(spans):
    m = _registry()
    keys = np.arange(32, dtype=np.int32)
    m.insert(keys, keys + 1)
    m.get(keys)                           # compile outside the count
    spans.clear()
    np.testing.assert_array_equal(m.get(keys), keys + 1)
    assert spans.syncs() == ["registry.sync.batch"]


def test_spine_round_spans_and_seven_syncs(spans):
    """The name counts the seven reads a round made before the registry
    packed its four into one; the test pins the four that remain."""
    m = MetricsRegistry()
    registry = _registry()
    qspec = QueueSpec(capacity=256)
    req_q, resp_q = DurableQueue(qspec), DurableQueue(qspec)
    rng = np.random.default_rng(1)
    ops, keys = _batch(rng)
    ops[-8:] = OP_NOP                     # a padded round
    bench_serve._spine_round(m, registry, req_q, resp_q, qspec, keys, ops)
    spans.clear()
    n = bench_serve._spine_round(m, registry, req_q, resp_q, qspec, keys,
                                 ops)
    assert n == 56
    names = spans.names()
    for step in ("ack", "dispatch", "commit", "force"):
        assert names.count(f"spine.{step}") == 1
    assert names.count("queue.enqueue") == 2
    assert names.count("queue.dequeue") == 2
    assert spans.syncs() == ["registry.sync.batch"] + ["queue.sync.ok"] * 3
    parent = dict(spans.events)
    assert parent["registry.route"] == "spine.dispatch"
    assert parent["queue.sync.ok"] == "spine.force"
    hists = m.snapshot()["histograms"]
    for step in ("ack", "dispatch", "commit", "force"):
        assert hists[f"span.spine.{step}"]["count"] == 2


def test_crash_and_recover_spans(spans):
    m = _registry()
    keys = np.arange(100, dtype=np.int32)
    m.insert(keys)
    spans.clear()
    m.crash_and_recover(seed=3)
    events = spans.events
    assert events[0] == ("registry.recover", None)
    inner = [n for n, p in events if p == "registry.recover"]
    assert inner == ["registry.crash", "registry.rebuild",
                     "registry.sync.recover_hist",
                     "registry.sync.recover_ready",
                     "registry.sync.overflow"]
    assert spans.syncs() == inner[2:]
    assert np.asarray(m.contains(keys)).all()     # completed inserts


def test_durable_queue_reads_are_syncs(spans):
    q = DurableQueue(QueueSpec(capacity=64))
    vals = np.arange(8, dtype=np.int32)
    q.enqueue(vals)
    q.dequeue(2)
    q.peek(2)                             # compile outside the count
    spans.clear()
    q.enqueue(vals)
    assert spans.names() == ["queue.enqueue", "queue.sync.tickets",
                             "queue.sync.overflow"]
    spans.clear()
    got, ok = q.dequeue(2)
    assert spans.names() == ["queue.dequeue", "queue.sync.vals",
                             "queue.sync.ok"]
    np.testing.assert_array_equal(got[ok], [2, 3])
    spans.clear()
    q.peek(2)
    assert spans.syncs() == ["queue.sync.vals", "queue.sync.ok"]


def test_router_v1_reads_its_dropped_count(spans):
    m = ShardedDurableMap(SetSpec(capacity=1024, backend="probe"),
                          n_shards=4, router="v1")
    rng = np.random.default_rng(2)
    m.apply(*_batch(rng))                 # compile outside the count
    spans.clear()
    m.apply(*_batch(rng))
    assert spans.syncs() == ["registry.sync.dropped",
                             "registry.sync.overflow"]


def test_elastic_map_overflow_latch_is_a_sync(spans):
    m = ElasticShardedMap(SetSpec(capacity=256, backend="probe"),
                          n_shards=2)
    spans.clear()
    assert not m.overflowed
    assert spans.syncs() == ["registry.sync.overflow"]


# ---------------------------------------------------------------------------
# 3. The snapshot restart (DESIGN.md §11)
# ---------------------------------------------------------------------------


def _snapshotted(tmp_path):
    from repro.store.snapshot import Snapshotter
    m = ShardedDurableMap(SetSpec(capacity=1024, backend="bucket"),
                          n_shards=4, metrics=MetricsRegistry(),
                          metrics_name="registry")
    return m, Snapshotter(m, str(tmp_path / "snap"))


def test_snapshot_spans(spans, tmp_path):
    m, sn = _snapshotted(tmp_path)
    m.insert(np.arange(1, 100, dtype=np.int32))
    spans.clear()
    sn.snapshot()
    sn.wait()
    assert spans.events == [("registry.snapshot.capture", None),
                            ("registry.snapshot.build", None),
                            ("registry.snapshot.save", None)]
    sn.close()


def test_hybrid_restart_spans_and_three_syncs(spans, tmp_path):
    m, sn = _snapshotted(tmp_path)
    keys = np.arange(1, 200, dtype=np.int32)
    m.insert(keys[:150])
    sn.snapshot()
    sn.wait()
    m.insert(keys[150:])
    spans.clear()
    sn.recover(np.random.default_rng(4).random(
        m.state.cur.shape).astype(np.float32))
    top = [n for n, p in spans.events if p is None]
    assert top == ["registry.snapshot.restore", "registry.recover",
                   "registry.snapshot.restore"]
    inner = [n for n, p in spans.events if p == "registry.recover"]
    assert inner == ["registry.crash", "registry.sync.delta",
                     "registry.delta", "registry.snapshot.load",
                     "registry.rebuild", "registry.sync.recover_ready",
                     "registry.sync.overflow"]
    assert spans.syncs() == ["registry.sync.delta",
                             "registry.sync.recover_ready",
                             "registry.sync.overflow"]
    c = m._m.snapshot()["counters"]
    assert c["registry.recoveries_hybrid"] == 1
    assert c["registry.recovery_psyncs"] == 0
    assert c.get("registry.recover_fallbacks", 0) == 0
    assert np.asarray(m.contains(keys)).all()     # completed inserts
    sn.close()


def test_restart_without_a_snapshot_counts_a_fallback(spans, tmp_path):
    m, sn = _snapshotted(tmp_path)
    m.insert(np.arange(1, 50, dtype=np.int32))
    spans.clear()
    sn.recover()
    top = [n for n, p in spans.events if p is None]
    assert top == ["registry.recover", "registry.snapshot.restore"]
    c = m._m.snapshot()["counters"]
    assert c["registry.recover_fallbacks"] == 1
    assert c.get("registry.recoveries_hybrid", 0) == 0
    assert c["registry.recovery_psyncs"] == 0
    sn.close()
