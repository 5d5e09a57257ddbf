"""The benchmark cell ``set_snapshot_recover`` on the CPU, at the tiny
sizes of the driver tests (2048 slots, 4 shards, 128-lane batches).

A whole run (``bench.run.execute``) through the cell's own driver comes
out correct against the plain reference over several snapshot / crash
cycles, every crash restored through the snapshot.  It comes out not
correct when a restart loses one delta slot, when the snapshot's
watermark is one epoch too high, when the restart falls back to the full
scan, and with the control (buffered durability, no snapshots) in the
registry's place.  A restart at the driver's shapes is bit-identical to
the full rebuild under the same adversary.
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, sut  # noqa: E402
from bench.drivers import snapshot_crash_loop  # noqa: E402
from bench.run import execute  # noqa: E402
from bench.traffic_gen import KeyUniverse, OpStream, rng_for  # noqa: E402
from repro.core import ShardedDurableMap, shard  # noqa: E402
from repro.obs import MetricsRegistry  # noqa: E402
from repro.store.checkpoint import CheckpointManager  # noqa: E402
from repro.store.snapshot import Snapshotter  # noqa: E402

CELL = "set_snapshot_recover"
SMALL = dict(capacity=2048, shards=4, batch=128, key_range=2048)
SEED = 2**31 + 7


def small() -> dict:
    wl = harness.workload(CELL)
    wl["config"].update(SMALL)
    wl["traffic"]["ring_batches"] = 32
    return wl


def run(control: bool = False) -> dict:
    return execute(small(), SEED, 0.6, False, time.perf_counter(),
                   control=control)


def test_sound_run_is_correct():
    line = run()
    assert line["correct"], line["checks"]
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert "fallback_recoveries" in line["checks"]
    info = line["info"]
    assert info["crashes"] >= 3 and line["attempted"] == info["crashes"]
    warm = small()["traffic"]["warm_cycles"]
    assert info["recoveries_hybrid"] == info["crashes"] + warm
    assert info["snapshots"] == info["crashes"] + warm
    assert info["delta_slots_per_crash"] > 0
    assert info["restart_widths"][-1] >= 256
    assert set(line["metrics"]) == {"recover_ms", "setup_s"}


def fault_slot_left_out(monkeypatch):
    """The restart leaves the first delta slot of shard 0 out of the
    patch."""
    inner = shard.hybrid_recover

    def hybrid_recover(snap, persisted, keys, values, stamp, delta_idx,
                       **kw):
        delta_idx = delta_idx.at[0, 0].set(persisted.shape[1])
        return inner(snap, persisted, keys, values, stamp, delta_idx, **kw)
    monkeypatch.setattr(shard, "hybrid_recover", hybrid_recover)


def fault_watermark_high(monkeypatch):
    """The snapshot records a watermark one epoch above its capture."""
    inner = ShardedDurableMap.snapshot_capture

    def snapshot_capture(self):
        cap = inner(self)
        cap["watermark"] = cap["watermark"] + 1
        return cap
    monkeypatch.setattr(ShardedDurableMap, "snapshot_capture",
                        snapshot_capture)


def fault_fallback(monkeypatch):
    """No committed snapshot is found, so every restart scans the pool."""
    monkeypatch.setattr(CheckpointManager, "latest_step",
                        lambda self: None)


FAULTS = {"slot_left_out": fault_slot_left_out,
          "watermark_high": fault_watermark_high,
          "fallback": fault_fallback}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    line = run()
    assert not line["correct"], (fault, line["checks"])
    if fault == "fallback":
        assert line["checks"]["fallback_recoveries"]["value"] > 0
    else:
        assert line["checks"]["fallback_recoveries"]["value"] == 0


def test_control_is_not_correct():
    line = run(control=True)
    assert not line["correct"]
    assert line["checks"]["psync_gap"]["value"] > 0
    assert line["checks"]["fallback_recoveries"]["value"] > 0


def test_restart_bit_identical_to_full_rebuild(tmp_path):
    """The driver's path (its registry, prefill, snapshot and a cycle of
    batches) restarted through the snapshot equals the full rebuild of a
    copy under the same adversary, field by field."""
    wl = small()
    cfg, tr = wl["config"], wl["traffic"]
    m = sut.build_registry(cfg, MetricsRegistry())
    universe = KeyUniverse(SEED, cfg["key_range"], tr["prefill"])
    sut.prefill(m, universe.prefill, cfg["batch"], rng_for(SEED, 4))
    sn = Snapshotter(m, str(tmp_path / "snap"))
    sn.snapshot()
    stream = OpStream(tr, universe, SEED)
    for _ in range(cfg["snapshot"]["delta_batches"]):
        m.apply(*stream.draw(cfg["batch"]))
    sn.wait()
    full = sut.build_registry(cfg)
    full.state = jax.tree.map(jnp.array, m.state)
    u = rng_for(SEED, 5).random(m.state.cur.shape).astype(np.float32)
    full.crash_and_recover(u)
    sn.recover(u)
    sn.close()
    g = m._m.snapshot()["gauges"]
    assert 0 < g["registry.last_recovery_from_delta_slots"] < cfg["capacity"]
    for f, a, b in zip(m.state._fields, m.state, full.state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"field {f} diverged")


def test_max_delta_bounds_the_cycle():
    cfg = harness.workload(CELL)["config"]
    tr = harness.workload(CELL)["traffic"]
    # 16 batches x 1024 lanes x 1/2 updates over 8 shards: 1024 a shard,
    # a quarter of it below, doubled above
    assert snapshot_crash_loop.delta_bounds(cfg, tr) == (256, 2048)
    cfg = dict(cfg, **SMALL)
    # 16 x 128 x 1/2 over 4 shards: 256; doubled, the 512-slot shard pool
    assert snapshot_crash_loop.delta_bounds(cfg, tr) == (64, 512)
