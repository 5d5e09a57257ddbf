"""Snapshot + delta-log hybrid recovery (DESIGN.md §11).

Pins the PR-9 acceptance surface:

  * the ``dirs`` checkpoint layout commits atomically (tmp-dir rename):
    a crash ANYWHERE mid-save -- between plane writes, before the rename
    -- leaves ignored residue, never a half-snapshot selected as latest;
  * hybrid recovery (latest committed snapshot + the ``stamp > W`` delta)
    is BIT-IDENTICAL to the full-pool ``recovery_scan`` rebuild under the
    same crash adversary, across backends, modes, removes/slot-reuse,
    zero and large deltas, the sharded runtime, and the durable queue,
    on both sides of the bucket-index patch's O(delta) threshold;
  * the mutation path pays ZERO extra psyncs for snapshotting (the op
    stream doubles as the delta log) and recovery itself psyncs exactly 0;
  * OracleSet / OracleQueue conformance holds through a snapshot
    boundary; epoch/watermark discipline survives snapshot chains with no
    intervening commits and process restarts.
"""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DurableMap, DurableQueue, OracleQueue, OracleSet,
                        QueueSpec, SetSpec, ShardedDurableMap)
from repro.core import engine as E
from repro.core.nvm import FREE, VALID, hash32
from repro.kernels.hash_probe import ops as hp_ops
from repro.obs.metrics import MetricsRegistry
from repro.store.checkpoint import CheckpointManager
from repro.store.snapshot import SnapshotPolicy, Snapshotter


def _copy_state(state):
    return jax.tree.map(jnp.array, state)


def _assert_states_equal(got, want, skip=("n_psync", "n_ops")):
    for f, a, b in zip(got._fields, got, want):
        if f in skip:
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"field {f} diverged")


def _u(rng, shape):
    return jnp.asarray(rng.random(shape).astype(np.float32))


# ---------------------------------------------------------------------------
# dirs layout: atomic tmp-dir-rename commits
# ---------------------------------------------------------------------------


def test_dirs_layout_commit_and_reopen(tmp_path):
    d = str(tmp_path / "cm")
    cm = CheckpointManager(d, layout="dirs", keep=2)
    cm.save(1, {"a": np.arange(5), "n": {"b": np.ones((2, 2))}},
            extra={"watermark": 7})
    cm.save(2, {"a": np.arange(6), "n": {"b": np.zeros((2, 2))}},
            extra={"watermark": 9})
    assert cm.latest_step() == 2
    assert cm.extra() == {"watermark": 9}
    cm.close()
    cm2 = CheckpointManager(d, layout="dirs")    # restart: rescan the dir
    assert cm2.latest_step() == 2
    r = cm2.restore(2)
    np.testing.assert_array_equal(r["a"], np.arange(6))
    assert r["n/b"].shape == (2, 2)
    assert cm2.extra(1) == {"watermark": 7}
    cm2.close()


def test_dirs_layout_partial_saves_never_selected(tmp_path):
    d = str(tmp_path / "cm")
    cm = CheckpointManager(d, layout="dirs")
    cm.save(2, {"a": np.arange(4)})
    cm.close()
    # crash mid-save: tmp dir full of planes but never renamed
    os.makedirs(d + "/.tmp-step_000000000003")
    np.save(d + "/.tmp-step_000000000003/a.npy", np.arange(3))
    # crash after rename that somehow lost a leaf: manifest re-verified
    shutil.copytree(d + "/step_000000000002", d + "/step_000000000004")
    os.remove(d + "/step_000000000004/a.npy")
    # unreadable manifest == not committed
    os.makedirs(d + "/step_000000000005")
    with open(d + "/step_000000000005/manifest.json", "w") as f:
        f.write("{truncated")
    cm2 = CheckpointManager(d, layout="dirs")
    assert cm2.latest_step() == 2, cm2.committed
    cm2.close()


def test_dirs_layout_gc_keeps_newest(tmp_path):
    d = str(tmp_path / "cm")
    cm = CheckpointManager(d, layout="dirs", keep=2)
    for s in (1, 2, 3):
        cm.save(s, {"a": np.full((4,), s)})
    assert cm.committed == [2, 3]
    assert not os.path.exists(d + "/step_000000000001")
    assert cm.restore(3)["a"].tolist() == [3, 3, 3, 3]
    cm.close()


# ---------------------------------------------------------------------------
# bit-identity: hybrid == full-pool rebuild, field by field
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,mode", [("bucket", "soft"),
                                          ("scan", "soft"),
                                          ("bucket", "linkfree")])
def test_map_hybrid_bit_identical(tmp_path, backend, mode, n=1024):
    rng = np.random.default_rng(3)
    m = DurableMap(SetSpec(capacity=n, backend=backend, mode=mode))
    sn = Snapshotter(m, str(tmp_path / "snap"))
    keys = (rng.permutation(5 * n)[: n // 2] + 1).astype(np.int32)
    m.insert(keys[: n // 4], keys[: n // 4] * 3)
    m.remove(keys[: n // 16])                # pre-snapshot DELETED slots
    sn.snapshot()
    sn.wait()
    m.insert(keys[n // 4:])                  # delta: fresh inserts,
    m.remove(keys[n // 8: n // 4])           # removes of snapshotted keys,
    m.insert(keys[: n // 16])                # reuse of pre-snapshot slots
    ref = DurableMap(m.spec)
    ref.state = _copy_state(m.state)
    u = _u(rng, n)
    ref.crash_and_recover(u)
    sn.recover(u)
    _assert_states_equal(m.state, ref.state)
    np.testing.assert_array_equal(m.last_recovery_hist,
                                  ref.last_recovery_hist)
    assert m.psyncs == 0                     # recovery psyncs: exactly 0
    sn.close()


def test_map_hybrid_zero_delta(tmp_path):
    rng = np.random.default_rng(4)
    m = DurableMap(SetSpec(capacity=256, backend="bucket"),
                   metrics=MetricsRegistry())
    sn = Snapshotter(m, str(tmp_path / "snap"))
    m.insert(np.arange(1, 100, dtype=np.int32))
    sn.snapshot()
    sn.wait()
    ref = DurableMap(m.spec)
    ref.state = _copy_state(m.state)
    u = _u(rng, 256)
    ref.crash_and_recover(u)
    sn.recover(u)                            # nothing stamped past W
    _assert_states_equal(m.state, ref.state)
    g = m._m.snapshot()["gauges"]
    assert g["map.last_recovery_from_delta_slots"] == 0
    assert g["map.last_recovery_from_snapshot_slots"] == 256
    sn.close()


def test_queue_hybrid_bit_identical(tmp_path):
    rng = np.random.default_rng(5)
    q = DurableQueue(QueueSpec(capacity=512))
    sn = Snapshotter(q, str(tmp_path / "snap"))
    q.enqueue(np.arange(1, 200, dtype=np.int32))
    sn.snapshot()
    sn.wait()
    q.dequeue(150)                           # delta: head moves past W
    q.enqueue(np.arange(300, 420, dtype=np.int32))
    ref = DurableQueue(q.spec)
    ref.state = _copy_state(q.state)
    u = _u(rng, 512)
    ref.crash_and_recover(u)
    sn.recover(u)
    _assert_states_equal(q.state, ref.state)
    np.testing.assert_array_equal(q.last_recovery_hist,
                                  ref.last_recovery_hist)
    assert q.psyncs == 0
    sn.close()


def test_queue_hybrid_drained_to_empty(tmp_path):
    """head/tail reconstruction when every live snapshot ticket was
    dequeued in the delta: head == tail == one past the last dequeue."""
    q = DurableQueue(QueueSpec(capacity=64))
    sn = Snapshotter(q, str(tmp_path / "snap"))
    q.enqueue([1, 2, 3, 4, 5])
    sn.snapshot()
    sn.wait()
    q.dequeue(5)
    ref = DurableQueue(q.spec)
    ref.state = _copy_state(q.state)
    ref.crash_and_recover()
    sn.recover()
    _assert_states_equal(q.state, ref.state)
    assert int(q.state.head) == int(q.state.tail) == 5
    sn.close()


def test_sharded_hybrid_bit_identical(tmp_path):
    rng = np.random.default_rng(6)
    mk = lambda: ShardedDurableMap(SetSpec(capacity=1024, backend="bucket"),
                                   n_shards=4)
    m = mk()
    sn = Snapshotter(m, str(tmp_path / "snap"))
    keys = (rng.permutation(8192)[:400] + 1).astype(np.int32)
    m.insert(keys[:250], keys[:250] * 7)
    sn.snapshot()                            # pipeline_flush + per-shard W
    sn.wait()
    m.insert(keys[250:])
    m.remove(keys[:100])
    ref = mk()
    ref.state = _copy_state(m.state)
    u = _u(rng, m.state.cur.shape)
    ref.crash_and_recover(u)
    sn.recover(u)
    _assert_states_equal(m.state, ref.state)
    sn.close()


# ---------------------------------------------------------------------------
# the bucket-index patch: O(delta) below the candidate bound, whole pool
# above it (engine.hybrid_patch_path), bit-identical either way
# ---------------------------------------------------------------------------

# case: (SetSpec kwargs, shards, live keys at the snapshot, delta inserts,
#        delta removes, expected patch).  Removes run first, so the inserts
#        re-use the slots they free.
PATCH_CASES = {
    "map": (dict(capacity=4096), None, 400, 40, 20, "delta"),
    "sharded": (dict(capacity=4 * 2048), 4, 600, 60, 30, "delta"),
    # 64 buckets of 8 ways for 560 keys: spills kept and re-packed
    "crowded": (dict(capacity=4096, n_buckets=64, stash_size=256), None,
                560, 40, 20, "delta"),
    # the delta's inserts spill past the 24-slot stash: the latch fires
    "stash_overflow": (dict(capacity=4096, n_buckets=32, bucket_width=4,
                            stash_size=24), None, 120, 48, 0, "delta"),
    "zero_delta": (dict(capacity=4096), None, 400, 0, 0, "delta"),
    # 200 delta slots pad to 256: K = 2*256*8 + 128 + 256 >= 1024
    "whole_pool": (dict(capacity=1024), None, 300, 150, 60, "full"),
    # the snapshot's own stash overflowed: its index misses members
    "snapshot_overflowed": (dict(capacity=4096, n_buckets=32,
                                 bucket_width=4, stash_size=16), None,
                            200, 10, 5, "full"),
}


@pytest.mark.parametrize("case", list(PATCH_CASES))
def test_hybrid_patch_bit_identical(tmp_path, case):
    kw, shards, n_live, n_ins, n_rem, path = PATCH_CASES[case]
    spec = SetSpec(backend="bucket", **kw)
    if shards is None:
        mk = lambda **m: DurableMap(spec, **m)
    else:
        mk = lambda **m: ShardedDurableMap(spec, n_shards=shards, **m)
    m = mk(metrics=MetricsRegistry())
    name = m._m_name
    rng = np.random.default_rng(sorted(PATCH_CASES).index(case))
    keys = (rng.permutation(1 << 20)[:n_live + n_ins] + 1).astype(np.int32)
    m.insert(keys[:n_live], keys[:n_live] * 5)
    sn = Snapshotter(m, str(tmp_path / "snap"))
    sn.snapshot()
    sn.wait()
    snap_planes = {f: np.asarray(getattr(m.state, f))
                   for f in ("keys", "cur", "sids", "overflow")}
    if n_rem:
        m.remove(keys[:n_rem])
    if n_ins:
        m.insert(keys[n_live:], keys[n_live:] * 5)
    ref = mk()
    ref.state = _copy_state(m.state)
    u = _u(rng, m.state.cur.shape)
    ref.crash_and_recover(u)
    sn.recover(u)
    _assert_states_equal(m.state, ref.state)
    np.testing.assert_array_equal(m.last_recovery_hist,
                                  ref.last_recovery_hist)
    assert m.psyncs == 0
    c = m._m.snapshot()["counters"]
    other = {"delta": "full", "full": "delta"}[path]
    assert c[f"{name}.hybrid_patch_{path}"] == 1
    assert c.get(f"{name}.hybrid_patch_{other}", 0) == 0
    # the case exercises what its name says
    snap_ovf = bool(np.any(snap_planes["overflow"]))
    assert snap_ovf == (case == "snapshot_overflowed")
    if case == "stash_overflow":
        assert m.overflowed and ref.overflowed
    if case == "crowded":
        before = set(snap_planes["sids"][snap_planes["sids"] >= 0].tolist())
        after = set(np.asarray(m.state.sids)[
            np.asarray(m.state.sids) >= 0].tolist())
        assert before & after and before != after    # kept and re-packed
    if case == "map":
        # a removed slot re-used by a key of another bucket
        nb, _ = spec.bucket_geometry()
        old, new = snap_planes["keys"], np.asarray(m.state.keys)
        live = (snap_planes["cur"] == VALID) \
            & (np.asarray(m.state.cur) == VALID)
        h = lambda k: np.asarray(hash32(jnp.asarray(k))) % nb
        assert (live & (old != new) & (h(old) != h(new))).any()
    sn.close()


def _patch_inputs(seed, n, nb, w, s, d, fill):
    """A canonical snapshot of a random member set, and a random delta of
    up to ``d`` slots over it (removes, fresh inserts, re-keyed slots and
    keys kept in place), as ``_delta_bucket_patch`` takes them."""
    rng = np.random.default_rng(seed)
    spec = SetSpec(capacity=n, backend="bucket", n_buckets=nb,
                   bucket_width=w, stash_size=s)
    cur = np.where(rng.random(n) < fill, VALID, FREE).astype(np.int32)
    keys = np.where(cur == VALID, rng.integers(1, 1 << 30, n),
                    0).astype(np.int32)
    index = hp_ops.bucket_init(jnp.asarray(keys), jnp.asarray(cur),
                               nb=nb, w=w, s=s)
    snap = E.make_state(spec)._replace(
        keys=jnp.asarray(keys), cur=jnp.asarray(cur),
        **dict(zip(("bkeys", "bids", "skeys", "sids", "stash_n",
                    "overflow"), index)))
    used = int(rng.integers(d // 2, d + 1))
    slots = np.sort(rng.choice(n, used, replace=False)).astype(np.int32)
    delta = np.full((d,), n, np.int32)
    delta[:used] = slots
    member = np.zeros((d,), bool)
    member[:used] = rng.random(used) < 0.6
    same = rng.random(used) < 0.3             # key kept in place
    new_keys = np.where(same & (keys[slots] != 0), keys[slots],
                        rng.integers(1, 1 << 30, used)).astype(np.int32)
    keys2, cur2 = keys.copy(), cur.copy()
    keys2[slots] = np.where(member[:used], new_keys, 0)
    cur2[slots] = np.where(member[:used], VALID, FREE)
    valid = delta < n
    return spec, snap, (jnp.asarray(keys2), jnp.asarray(cur2),
                        jnp.asarray(np.where(valid, delta, 0)),
                        jnp.asarray(valid), jnp.asarray(member),
                        jnp.asarray(delta))


# n=512, 64 buckets of 4 ways, a 24-slot stash: K = 9d + 24 < 512 up to
# d = 32, so widths 8-32 take the delta patch and 64-128 the whole pool
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("d,path", [(8, "delta"), (16, "delta"),
                                    (32, "delta"), (64, "full"),
                                    (128, "full")])
def test_delta_bucket_patch_matches_bucket_init(seed, d, path):
    n, nb, w, s = 512, 64, 4, 24
    spec, snap, args = _patch_inputs(seed, n, nb, w, s, d, fill=0.3)
    assert E.hybrid_patch_path(spec, d) == path
    assert not bool(snap.overflow)            # a sound snapshot
    got = E._delta_bucket_patch(snap, *args, spec=spec)
    want = hp_ops.bucket_init(args[0], args[1], nb=nb, w=w, s=s)
    for f, a, b in zip(("bkeys", "bids", "skeys", "sids", "stash_n",
                        "overflow"), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{f} diverged")


# ---------------------------------------------------------------------------
# crash-kill during an in-flight async snapshot
# ---------------------------------------------------------------------------


def _kill_after(monkeypatch, n_calls):
    """Kill the save after ``n_calls`` plane writes: np.save raises, the
    build thread dies mid-save, the tmp dir is left partially written --
    exactly what SIGKILL between plane writes leaves behind."""
    real_save, calls = np.save, [0]

    def killer(f, arr, *a, **kw):
        calls[0] += 1
        if calls[0] > n_calls:
            raise RuntimeError("simulated kill-9 between plane writes")
        return real_save(f, arr, *a, **kw)

    monkeypatch.setattr("repro.store.checkpoint.np.save", killer)


def test_crash_kill_between_plane_writes(tmp_path, monkeypatch):
    rng = np.random.default_rng(8)
    m = DurableMap(SetSpec(capacity=512, backend="bucket"),
                   metrics=MetricsRegistry())
    sn = Snapshotter(m, str(tmp_path / "snap"))
    m.insert(np.arange(1, 150, dtype=np.int32))
    sn.snapshot()
    sn.wait()                                # snapshot 1: committed
    m.insert(np.arange(200, 280, dtype=np.int32))
    _kill_after(monkeypatch, 2)              # snapshot 2 dies mid-save
    sn.snapshot()
    m.remove(np.arange(1, 40, dtype=np.int32))   # delta keeps growing
    ref = DurableMap(m.spec)
    ref.state = _copy_state(m.state)
    u = _u(rng, 512)
    ref.crash_and_recover(u)
    sn.recover(u)                            # prior snapshot + larger delta
    _assert_states_equal(m.state, ref.state)
    assert sn.store.latest_step() == 1       # the dead build never commits
    g = m._m.snapshot()["gauges"]
    assert g["map.last_recovery_from_delta_slots"] > 0
    sn.close()


def test_crash_kill_before_rename(tmp_path, monkeypatch):
    """Kill at the worst point: every plane + manifest written, rename not
    reached.  The full tmp dir is ignored and a RESTARTED snapshotter
    (fresh directory scan) recovers through the prior snapshot."""
    rng = np.random.default_rng(9)
    m = DurableMap(SetSpec(capacity=256, backend="scan"))
    d = str(tmp_path / "snap")
    sn = Snapshotter(m, d)
    m.insert(np.arange(1, 80, dtype=np.int32))
    sn.snapshot()
    sn.wait()
    m.insert(np.arange(100, 140, dtype=np.int32))
    monkeypatch.setattr("repro.store.checkpoint.os.rename",
                        lambda *a: (_ for _ in ()).throw(
                            RuntimeError("simulated kill-9 before rename")))
    f = sn.snapshot()
    with pytest.raises(RuntimeError):
        f.result()
    monkeypatch.undo()
    ref = DurableMap(m.spec)
    ref.state = _copy_state(m.state)
    u = _u(rng, 256)
    ref.crash_and_recover(u)
    sn.close()
    sn2 = Snapshotter(m, d)                  # restart: rescan the store dir
    assert sn2.store.latest_step() == 1
    assert any(fn.startswith(".tmp-") for fn in os.listdir(d))
    sn2.recover(u)
    _assert_states_equal(m.state, ref.state)
    sn2.close()


def test_recover_with_no_snapshot_falls_back(tmp_path):
    m = DurableMap(SetSpec(capacity=128, backend="bucket"),
                   metrics=MetricsRegistry())
    sn = Snapshotter(m, str(tmp_path / "snap"))
    m.insert([1, 2, 3])
    sn.recover()
    assert m.contains([1, 2, 3]).tolist() == [True] * 3
    g = m._m.snapshot()["gauges"]
    assert g["map.last_recovery_from_snapshot_slots"] == 0
    assert g["map.last_recovery_from_delta_slots"] == 128
    sn.close()


# ---------------------------------------------------------------------------
# zero hot-path cost + oracle conformance through the snapshot boundary
# ---------------------------------------------------------------------------


def test_snapshots_add_zero_hot_path_psyncs(tmp_path):
    """The op stream IS the delta log: the same trace with snapshots
    interleaved pays exactly the same psyncs as without."""
    rng = np.random.default_rng(10)
    a = DurableMap(SetSpec(capacity=512, backend="bucket"))
    b = DurableMap(SetSpec(capacity=512, backend="bucket"))
    sn = Snapshotter(b, str(tmp_path / "snap"),
                     SnapshotPolicy(every_steps=2))
    for step in range(6):
        keys = (rng.integers(1, 400, 32)).astype(np.int32)
        ops = rng.integers(0, 3, 32).astype(np.int32)
        a.apply(ops, keys)
        b.apply(ops, keys)
        sn.maybe_snapshot(step)
    sn.wait()
    assert a.psyncs == b.psyncs
    assert a.ops == b.ops
    sn.close()


def test_oracle_set_conformance_through_snapshot(tmp_path):
    rng = np.random.default_rng(11)
    m = DurableMap(SetSpec(capacity=128, backend="bucket"))
    sn = Snapshotter(m, str(tmp_path / "snap"))
    o = OracleSet(64)
    trace = [("insert" if r < 0.6 else "remove", int(k))
             for r, k in zip(rng.random(40), rng.integers(0, 32, 40))]
    for i, (kind, key) in enumerate(trace):
        if kind == "insert":
            o.insert(key, key * 10)
            m.insert([key], [key * 10])
        else:
            o.remove(key)
            m.remove([key])
        if i == len(trace) // 2:
            sn.snapshot()                    # boundary mid-trace
            sn.wait()
    sn.recover(_u(rng, 128))
    got = np.asarray(m.contains(np.arange(32)))
    ok, msg = o.check_recovery({k: 1 for k in range(32) if got[k]})
    assert ok, msg
    sn.close()


def test_oracle_queue_conformance_through_snapshot(tmp_path):
    rng = np.random.default_rng(12)
    q = DurableQueue(QueueSpec(capacity=32))
    sn = Snapshotter(q, str(tmp_path / "snap"))
    o = OracleQueue(32)
    for i in range(60):
        if rng.random() < 0.6:
            v = int(rng.integers(1, 99))
            if o.enqueue(v):
                pass
            q.enqueue([v])
        else:
            o.dequeue()
            q.dequeue(1)
        if i == 30:
            sn.snapshot()
            sn.wait()
    sn.recover(_u(rng, 32))
    contents, head, tail = OracleQueue.recover(o.crash([0] * 32))
    assert (int(q.state.head), int(q.state.tail)) == (head, tail)
    vals, ok = q.dequeue(len(contents))
    np.testing.assert_array_equal(np.asarray(vals)[np.asarray(ok)],
                                  contents)
    sn.close()


# ---------------------------------------------------------------------------
# watermark / epoch discipline + policy + probe fallback
# ---------------------------------------------------------------------------


def test_epoch_discipline_without_commits(tmp_path):
    """Back-to-back snapshots with NO intervening commits bump the stored
    watermark past every stamp on NVM; recovery must still raise the epoch
    strictly above it or later commits would stamp below the watermark and
    be invisible to the next delta scan."""
    m = DurableMap(SetSpec(capacity=128, backend="scan"))
    sn = Snapshotter(m, str(tmp_path / "snap"))
    m.insert([1, 2, 3])
    sn.snapshot()
    sn.wait()
    sn.snapshot()
    sn.wait()
    w = sn.store.extra()["watermark"]
    sn.recover()
    assert int(m.state.epoch) > w
    m.insert([9])
    assert int(np.asarray(m.state.stamp).max()) > w
    ref = DurableMap(m.spec)
    ref.state = _copy_state(m.state)
    ref.crash_and_recover()
    sn.recover()                             # the [9] commit is in the delta
    _assert_states_equal(m.state, ref.state)
    sn.close()


def test_snapshot_policy_cadence(tmp_path):
    m = DurableMap(SetSpec(capacity=64, backend="bucket"))
    sn = Snapshotter(m, str(tmp_path / "snap"),
                     SnapshotPolicy(every_steps=3))
    m.insert([1])
    assert sn.maybe_snapshot(1) is None
    assert sn.maybe_snapshot(2) is None
    f = sn.maybe_snapshot(3)
    assert f is not None
    sn.wait()
    assert sn.store.latest_step() == 3
    assert sn.maybe_snapshot(4) is None      # cadence restarts at 3
    sn.close()


def test_probe_backend_falls_back_to_full_scan(tmp_path):
    m = DurableMap(SetSpec(capacity=64, backend="probe"))
    sn = Snapshotter(m, str(tmp_path / "snap"))
    assert not sn.supports_hybrid
    assert sn.maybe_snapshot(100) is None    # snapshotter is inert
    with pytest.raises(ValueError):
        sn.snapshot()
    m.insert([4, 5])
    sn.recover()
    assert m.contains([4, 5]).tolist() == [True, True]
    sn.close()


def test_snapshot_metrics_surface(tmp_path):
    m = DurableMap(SetSpec(capacity=256, backend="bucket"),
                   metrics=MetricsRegistry())
    sn = Snapshotter(m, str(tmp_path / "snap"))
    m.insert(np.arange(1, 100, dtype=np.int32))
    sn.snapshot()
    sn.wait()
    m.insert(np.arange(100, 130, dtype=np.int32))
    sn.recover()
    snap = m._m.snapshot()
    assert snap["counters"]["map.snapshots"] == 1
    assert snap["counters"]["map.snapshot_bytes_written"] > 0
    assert snap["counters"]["map.recovery_psyncs"] == 0
    assert snap["histograms"]["span.map.snapshot"]["count"] == 1
    assert snap["gauges"]["map.snapshot_age_seconds"] > 0
    assert snap["gauges"]["map.last_recovery_from_delta_slots"] == 30
    assert snap["gauges"]["map.last_recovery_from_snapshot_slots"] == 226
    c = snap["collected"]["map.snapshotter"]
    assert c["snapshots"] == 1 and c["latest_step"] == 1
    sn.close()
