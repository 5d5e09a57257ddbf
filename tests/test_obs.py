"""Observability layer suite (DESIGN.md §10).

  1. PRIMITIVES -- counter monotonicity, gauge levels, the log2-bucket
     histogram's exact sample-based p50/p99/p999 (checked against
     numpy on the retained samples) and its graceful subsampling
     degradation past ``max_samples`` (``exact`` flips false, count/sum
     stay exact).
  2. REGISTRY + SINKS -- create-on-first-use accessors, span timers,
     collector crossing at snapshot time only, ``reset_volatile``
     (histograms/gauges clear, counters survive), InMemory/JSONL sinks.
  3. BRIDGE -- monotone lifetime totals over device counters that
     recovery resets, announced (``mark_reset``) and un-announced.
  4. COUNTER DURABILITY -- for all three set backends, the sharded
     facade, and the queue: volatile per-state counters reset at
     ``crash_and_recover`` while the registry's ``*_total`` counters
     stay monotone, and recovery itself psyncs exactly 0.
  5. MID-PIPELINE CRASH (regression) -- the ``pipeline_abandoned``
     registry counter and ``scratch_stats()`` agree after a crash
     abandons a staged batch: every acquired scratch set is released
     (acquires == releases once the pipeline is empty), nothing leaks.
"""
import json

import numpy as np
import pytest

from repro.core import (DurableMap, DurableQueue, QueueSpec,
                        SetSpec, ShardedDurableMap)
from repro.core import router as RT
from repro.obs import (Counter, DeviceCounterBridge, Gauge, Histogram,
                       InMemorySink, JSONLSink, MetricsRegistry, Sink)

BACKENDS = ("probe", "scan", "bucket")


# ---------------------------------------------------------------------------
# 1. Primitives
# ---------------------------------------------------------------------------


def test_counter_monotone():
    c = Counter()
    c.inc()
    c.inc(41)
    assert c.value == 42
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 42


def test_gauge_last_write_wins():
    g = Gauge()
    g.set(7)
    g.set(3.5)
    assert g.value == 3.5


def test_histogram_exact_percentiles_match_numpy():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-7, sigma=1.5, size=20_000)
    h = Histogram()
    for chunk in np.array_split(samples, 13):      # multi-chunk append path
        h.record_many(chunk)
    assert h.count == samples.size
    for q in (50, 99, 99.9):
        assert h.percentile(q) == pytest.approx(
            np.percentile(samples, q, method="nearest"), rel=0, abs=0)
    snap = h.snapshot()
    assert snap["exact"] is True
    assert snap["count"] == samples.size
    assert snap["min"] == samples.min()
    assert snap["max"] == samples.max()
    assert snap["mean"] == pytest.approx(samples.mean())
    # every retained sample lands in exactly one log2 bucket
    assert sum(snap["buckets_log2ns"].values()) == samples.size


def test_histogram_log2_buckets():
    h = Histogram()
    # 1ns -> bucket 0; ~1us -> bucket 9 ([512, 1024)ns); 1.5us -> bucket 10
    h.record(1e-9)
    h.record(600e-9)
    h.record(1500e-9)
    b = h.buckets()
    assert b[0] == 1 and b[9] == 1 and b[10] == 1 and b.sum() == 3


def test_histogram_subsampling_degrades_gracefully():
    h = Histogram(max_samples=1024)
    vals = np.arange(1, 5001, dtype=np.float64) * 1e-6
    h.record_many(vals)
    snap = h.snapshot()
    assert snap["exact"] is False          # reservoir degraded, and says so
    assert snap["count"] == 5000           # exact accounting survives
    assert snap["sum"] == pytest.approx(vals.sum())
    assert snap["min"] == vals[0] and snap["max"] == vals[-1]
    # subsampled quantiles stay in the right neighborhood
    assert snap["p50"] == pytest.approx(np.percentile(vals, 50), rel=0.05)


@pytest.mark.parametrize("max_samples", [1 << 25, 1000])
def test_histogram_keeps_a_running_retained_count(max_samples):
    # one record at a time, as a span records: the retained count is kept
    # as it goes (O(1) per record), never re-summed over the chunks
    rng = np.random.default_rng(5)
    vals = rng.lognormal(mean=-7, sigma=1.0, size=10_000)
    h = Histogram(max_samples=max_samples)
    for v in vals:
        h.record(v)
    assert h._retained == sum(c.size for c in h._chunks)
    assert h._retained <= max_samples
    snap = h.snapshot()
    assert snap["count"] == vals.size
    assert snap["sum"] == pytest.approx(vals.sum(), rel=1e-12)
    assert snap["min"] == vals.min() and snap["max"] == vals.max()
    if max_samples > vals.size:
        assert snap["exact"] is True
        for q in (50, 99, 99.9):
            assert h.percentile(q) == np.percentile(vals, q,
                                                    method="nearest")
    else:
        assert snap["exact"] is False
        assert snap["p50"] == pytest.approx(np.percentile(vals, 50),
                                            rel=0.1)
    assert h._retained == sum(c.size for c in h._chunks)


def test_empty_histogram_snapshot():
    snap = Histogram().snapshot()
    assert snap["count"] == 0
    assert snap["p50"] is None and snap["p999"] is None
    assert snap["buckets_log2ns"] == {}


# ---------------------------------------------------------------------------
# 2. Registry + sinks
# ---------------------------------------------------------------------------


def test_registry_create_on_first_use_and_snapshot():
    m = MetricsRegistry()
    m.counter("a.b").inc(3)
    m.gauge("depth").set(17)
    m.histogram("lat").record(2e-3)
    with m.span("stage"):
        pass
    m.register_collector("dev", lambda: {"x": 1})
    snap = m.snapshot()
    assert snap["counters"]["a.b"] == 3
    assert snap["gauges"]["depth"] == 17
    assert snap["histograms"]["lat"]["count"] == 1
    assert snap["histograms"]["span.stage"]["count"] == 1
    assert snap["histograms"]["span.stage"]["p50"] > 0
    assert snap["collected"]["dev"] == {"x": 1}


def test_collector_invoked_only_at_snapshot():
    m = MetricsRegistry()
    calls = []
    m.register_collector("lazy", lambda: calls.append(1) or {"n": len(calls)})
    m.counter("c").inc()          # metric traffic does not invoke collectors
    assert calls == []
    m.snapshot()
    m.snapshot()
    assert len(calls) == 2


def test_reset_volatile_keeps_counters():
    m = MetricsRegistry()
    m.counter("total").inc(5)
    m.gauge("g").set(9)
    m.histogram("h").record(1.0)
    m.reset_volatile()
    snap = m.snapshot()
    assert snap["counters"]["total"] == 5          # durable view survives
    assert snap["gauges"]["g"] == 0.0
    assert snap["histograms"]["h"]["count"] == 0


def test_sinks_receive_emitted_snapshots(tmp_path):
    mem = InMemorySink()
    path = str(tmp_path / "trail.jsonl")
    jl = JSONLSink(path)
    assert isinstance(mem, Sink) and isinstance(jl, Sink)
    m = MetricsRegistry(sinks=[mem, jl])
    m.counter("n").inc(np.int64(2))                # numpy scalars coerce
    m.emit(label="round-1")
    m.emit()
    jl.close()
    assert len(mem.records) == 2
    assert mem.records[0]["label"] == "round-1"
    lines = [json.loads(l) for l in open(path).read().splitlines()]
    assert len(lines) == 2 and lines[0]["counters"]["n"] == 2
    with pytest.raises(ValueError):
        jl.write({})


def test_bridge_monotone_over_resets():
    m = MetricsRegistry()
    b = DeviceCounterBridge(m, "s")
    b.fold(psync=10)
    b.fold(psync=25)
    assert b.total("psync") == 25
    b.mark_reset(psync=0)          # announced recovery: no double count
    b.fold(psync=7)
    assert b.total("psync") == 32
    b.fold(psync=3)                # UN-announced reset: count full value
    assert b.total("psync") == 35


# ---------------------------------------------------------------------------
# 4. Counter durability across crash_and_recover
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_map_counters_durable_across_recovery(backend):
    m = MetricsRegistry()
    d = DurableMap(capacity=256, backend=backend, metrics=m)
    keys = np.arange(40, dtype=np.int32)
    d.insert(keys, keys)
    d.remove(keys[:10])
    pre = m.snapshot()["collected"]["map"]
    assert pre["psyncs"] == pre["psync_total"] == 50
    d.crash_and_recover()
    d.contains(keys)
    post = m.snapshot()["collected"]["map"]
    assert post["psyncs"] == 0                 # volatile counter reset
    assert post["ops"] == 40                   # only the post-crash reads
    assert post["psync_total"] == 50           # durable total is monotone
    assert post["ops_total"] == 90
    assert post["recoveries"] == 1
    assert post["recovery_psyncs"] == 0        # recovery is psync-free
    assert post["last_recovery_seconds"] > 0
    assert m.snapshot()["gauges"]["map.last_recovery_scanned_slots"] == 256
    assert m.snapshot()["histograms"]["span.map.recovery"]["count"] == 1


def test_sharded_counters_durable_across_recovery():
    m = MetricsRegistry()
    d = ShardedDurableMap(capacity=256, n_shards=4, metrics=m)
    keys = np.arange(64, dtype=np.int32)
    d.insert(keys, keys)
    d.crash_and_recover()
    post = m.snapshot()["collected"]["sharded_map"]
    assert post["psyncs"] == 0
    assert post["psync_total"] == 64
    assert post["recoveries"] == 1 and post["recovery_psyncs"] == 0
    assert m.snapshot()["gauges"][
        "sharded_map.last_recovery_scanned_slots"] == 4 * 64


def test_queue_counters_durable_across_recovery():
    m = MetricsRegistry()
    q = DurableQueue(QueueSpec(capacity=64), metrics=m)
    q.enqueue(np.arange(8))
    q.dequeue(3)
    q.crash_and_recover()
    post = m.snapshot()["collected"]["queue"]
    assert post["psyncs"] == 0 and post["ops"] == 0
    assert post["psync_total"] == 11 and post["ops_total"] == 11
    assert post["recoveries"] == 1 and post["recovery_psyncs"] == 0
    assert post["size"] == 5                   # live elements survived
    # second cycle: totals keep climbing, never rewind
    q.enqueue([100])
    q.crash_and_recover()
    post2 = m.snapshot()["collected"]["queue"]
    assert post2["psync_total"] == 12 and post2["recoveries"] == 2


def test_reattach_after_recovery_replaces_collector():
    """latest-wins collector registration: a structure re-attached under
    the same name replaces its old closure instead of double-reporting."""
    m = MetricsRegistry()
    DurableMap(capacity=64, metrics=m, metrics_name="reg")
    d2 = DurableMap(capacity=64, metrics=m, metrics_name="reg")
    d2.insert([1, 2, 3])
    snap = m.snapshot()["collected"]
    assert list(snap) == ["reg"]
    assert snap["reg"]["psyncs"] == 3


# ---------------------------------------------------------------------------
# 5. Mid-pipeline crash: abandoned-batch accounting (regression)
# ---------------------------------------------------------------------------


def test_pipeline_crash_abandon_counter_and_scratch_agree():
    m = MetricsRegistry()
    d = ShardedDurableMap(capacity=512, n_shards=4, pipeline_depth=2,
                          metrics=m)
    s0 = d.scratch_stats()
    in_flight0 = s0["acquires"] - s0["releases"]
    keys = np.arange(32, dtype=np.int32)
    d.insert(keys, keys)                  # staged batch 1
    d.insert(keys + 100, keys)            # dispatches 1, stages 2
    d.crash_and_recover()                 # batch 2 is ABANDONED
    snap = m.snapshot()
    coll = snap["collected"]["sharded_map"]
    assert coll["pipeline_abandoned"] == 1
    assert snap["counters"]["sharded_map.pipeline_abandoned"] == 1
    # the abandoned batch's scratch was recycled, not leaked: with the
    # pipeline empty, every acquire since the baseline has a release
    s1 = d.scratch_stats()
    assert s1 == coll["scratch"]          # snapshot sees the same pool
    assert s1["acquires"] - s1["releases"] == in_flight0
    assert coll["pipeline_staged"] == 0 and coll["pipeline_pending"] == 0
    # only the dispatched batch's psyncs were ever issued
    assert coll["psync_total"] == 32
    # the abandoned insert is gone; the dispatched one survived
    assert not np.asarray(d.contains(keys + 100)).any()
    assert np.asarray(d.contains(keys)).all()


def test_scratch_pool_releases_counter():
    stats0 = RT.scratch_stats()
    d = ShardedDurableMap(capacity=256, n_shards=4)
    d.insert(np.arange(16, dtype=np.int32))
    stats1 = RT.scratch_stats()
    da = stats1["acquires"] - stats0["acquires"]
    dr = stats1["releases"] - stats0["releases"]
    assert da >= 1 and da == dr           # synchronous path: no leak


def test_bench_meta_names_the_device():
    """Every BENCH payload names the device its numbers came from."""
    import jax
    from repro.obs import bench_meta
    meta = bench_meta()
    dev = jax.devices()
    assert meta["platform"] == dev[0].platform
    assert meta["device_kind"] == dev[0].device_kind
    assert meta["device_count"] == len(dev)
