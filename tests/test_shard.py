"""Sharded-runtime tests: backend-conformance battery under S shards,
router correctness, lane-budget drop latch, psync parity with the
unsharded engine, parallel per-shard recovery, Pallas wiring under vmap,
and the opt-in shard_map multi-device path."""
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

import repro.kernels.hash_probe.ops as hp_ops
import repro.kernels.recovery_scan.ops as rs_ops
from repro.core import (DurableMap, ShardedDurableMap, SetSpec, ShardSpec,
                        MODES, OracleSet, OP_CONTAINS, OP_INSERT, OP_REMOVE,
                        OP_NOP, np_shard_of, shard_of)
from repro.core import shard as SH

BACKEND_NAMES = ("probe", "scan", "bucket")
SHARD_COUNTS = (1, 8)


# ---------------------------------------------------------------------------
# Conformance: the existing backend battery, now under the shard runtime.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
@pytest.mark.parametrize("backend", BACKEND_NAMES)
def test_sharded_backend_conformance_battery(backend, n_shards, mode):
    m = ShardedDurableMap(SetSpec(capacity=128, mode=mode, backend=backend),
                          n_shards=n_shards)
    ok = np.array(m.insert([5, 6, 7, 6], [50, 60, 70, 61]))
    assert list(ok) == [True, True, True, False]
    assert len(m) == 3
    assert list(np.array(m.contains([5, 6, 7, 8]))) == [True, True, True,
                                                        False]
    assert list(np.array(m.get([5, 6, 8], default=-1))) == [50, 60, -1]
    assert list(np.array(m.remove([6, 8, 6]))) == [True, False, False]
    # psync accounting is shard- and backend-independent: same counts as the
    # unsharded probe map on the same trace (get == contains for psyncs)
    probe = DurableMap(SetSpec(capacity=128, mode=mode))
    probe.insert([5, 6, 7, 6], [50, 60, 70, 61])
    probe.contains([5, 6, 7, 8])
    probe.contains([5, 6, 8])
    probe.remove([6, 8, 6])
    assert m.psyncs == probe.psyncs
    assert m.ops == probe.ops
    # crash + recovery (independent per-shard adversary) through the backend
    m.crash_and_recover(seed=7)
    assert list(np.array(m.contains([5, 6, 7]))) == [True, False, True]
    assert len(m) == 2
    assert m.last_recovery_hist_shards.shape == (n_shards, 5)
    assert int(m.last_recovery_hist[3]) == 2      # VALID bin == live members
    assert m.router_dropped == 0


@pytest.mark.parametrize("mode", ("soft", "linkfree"))
@pytest.mark.parametrize("backend", BACKEND_NAMES)
def test_sharded_matches_oracle_random_workload(backend, mode):
    rng = np.random.default_rng(11)
    m = ShardedDurableMap(SetSpec(capacity=128, mode=mode, backend=backend),
                          n_shards=4)
    o = OracleSet(128, mode=mode)
    for _ in range(10):
        op = rng.choice(["insert", "remove", "contains"])
        keys = rng.integers(0, 32, 8).astype(np.int32)
        if op == "insert":
            got = np.array(m.insert(keys, keys * 2))
            exp = [o.insert(int(k), int(k) * 2) for k in keys]
        elif op == "remove":
            got = np.array(m.remove(keys))
            exp = [o.remove(int(k)) for k in keys]
        else:
            got = np.array(m.contains(keys))
            exp = [o.contains(int(k)) for k in keys]
        assert list(got) == exp, (backend, mode, op, keys)


@pytest.mark.parametrize("backend", BACKEND_NAMES)
def test_sharded_apply_matches_unsharded_apply(backend):
    """A mixed batch through the routed vmapped dispatch returns lane-for-
    lane what the unsharded engine returns (shards are disjoint key spaces,
    so per-shard phase linearization composes to the global one)."""
    rng = np.random.default_rng(3)
    spec = SetSpec(capacity=256, mode="soft", backend=backend)
    a = ShardedDurableMap(spec, n_shards=8)
    b = DurableMap(spec)
    seed = np.arange(0, 24, dtype=np.int32)
    a.insert(seed, seed)
    b.insert(seed, seed)
    for _ in range(4):
        ops = rng.integers(0, 3, 16).astype(np.int32)
        keys = rng.integers(0, 40, 16).astype(np.int32)
        np.testing.assert_array_equal(np.array(a.apply(ops, keys, keys * 2)),
                                      np.array(b.apply(ops, keys, keys * 2)))
    assert len(a) == len(b)
    assert a.psyncs == b.psyncs and a.ops == b.ops
    probe_all = np.arange(40)
    np.testing.assert_array_equal(np.array(a.contains(probe_all)),
                                  np.array(b.contains(probe_all)))


# ---------------------------------------------------------------------------
# Router: partitioning, grid scatter/gather, lane budget, drop latch.
# ---------------------------------------------------------------------------

def test_shard_of_matches_np_and_partitions():
    keys = np.arange(4096, dtype=np.int32)
    for s in (1, 2, 8, 32):
        sid = np.array(shard_of(jnp.asarray(keys), s))
        np.testing.assert_array_equal(sid, np_shard_of(keys, s))
        assert sid.min() >= 0 and sid.max() < s
        if s > 1:       # high avalanching bits spread uniformly
            counts = np.bincount(sid, minlength=s)
            assert counts.min() > 0.5 * 4096 / s
            assert counts.max() < 2.0 * 4096 / s


def test_route_gather_roundtrip_preserves_lane_order():
    rng = np.random.default_rng(0)
    s, l = 4, 8
    keys = rng.integers(0, 1000, 24).astype(np.int32)
    ops = rng.integers(0, 3, 24).astype(np.int32)
    r_ops, r_keys, r_vals, slot, dropped = SH.route(
        jnp.asarray(ops), jnp.asarray(keys), jnp.asarray(keys * 3),
        n_shards=s, lane_budget=l)
    assert int(dropped) == 0
    sid = np_shard_of(keys, s)
    slot = np.array(slot)
    # every lane landed in its key's shard row, padding slots are NOPs
    assert (slot >= 0).all()
    np.testing.assert_array_equal(slot // l, sid)
    grid_ops = np.array(r_ops).reshape(-1)
    n_real = (grid_ops != OP_NOP).sum()
    assert n_real == 24
    np.testing.assert_array_equal(grid_ops[slot], ops)
    np.testing.assert_array_equal(np.array(r_keys).reshape(-1)[slot], keys)
    np.testing.assert_array_equal(np.array(r_vals).reshape(-1)[slot],
                                  keys * 3)
    # same-shard lanes keep their relative (priority) order
    for sh in range(s):
        lanes = np.where(sid == sh)[0]
        assert (np.diff(slot[lanes]) > 0).all()
    # gather inverts the scatter
    got = np.array(SH.gather(r_keys, jnp.asarray(slot), 0))
    np.testing.assert_array_equal(got, keys)


def test_lane_budget_rules():
    sp = ShardSpec(base=SetSpec(capacity=1024), n_shards=8)
    assert sp.lane_budget(8) == 8          # tiny batches: loss-free
    assert sp.lane_budget(32) == 32
    assert sp.lane_budget(1024) == 256     # 2 * 1024/8, pow2
    assert sp.lane_budget(100) == 32       # clamped up to min_lane_budget
    s1 = ShardSpec(base=SetSpec(capacity=1024), n_shards=1)
    assert s1.lane_budget(1024) == 1024    # single shard: identity routing
    wide = ShardSpec(base=SetSpec(capacity=1024), n_shards=8, lane_factor=4)
    assert wide.lane_budget(1024) == 512


def test_shard_spec_validation():
    with pytest.raises(ValueError, match="n_shards"):
        ShardSpec(base=SetSpec(capacity=64), n_shards=3)
    with pytest.raises(ValueError, match="lane_factor"):
        ShardSpec(base=SetSpec(capacity=64), lane_factor=0)
    # non-divisible totals round the per-shard pool UP to the next pow2
    # (a 13-slot pool would break the pow2 table/bucket invariants);
    # effective_capacity reports what was actually provisioned
    sp = ShardSpec(base=SetSpec(capacity=100), n_shards=8)
    assert sp.per_shard_capacity == 16
    assert sp.shard_spec().capacity == 16
    assert sp.effective_capacity == 128
    # even splits keep the exact quotient, pow2 or not
    even = ShardSpec(base=SetSpec(capacity=1000), n_shards=2)
    assert even.per_shard_capacity == 500
    assert even.effective_capacity == 1000


def test_facade_constructor_forms_agree():
    """All construction forms resolve to the same ShardSpec; an explicit
    n_shards overrides (never silently loses to) a passed ShardSpec."""
    base = SetSpec(capacity=128, backend="bucket")
    assert ShardedDurableMap(base).n_shards == 8            # default
    assert ShardedDurableMap(base, n_shards=4).n_shards == 4
    assert ShardedDurableMap(capacity=128, n_shards=4).n_shards == 4
    sspec = ShardSpec(base=base, n_shards=16)
    assert ShardedDurableMap(sspec).n_shards == 16
    assert ShardedDurableMap(sspec, n_shards=4).n_shards == 4
    m = ShardedDurableMap(sspec, lane_factor=3)
    assert m.sspec.lane_factor == 3 and m.n_shards == 16


def test_router_drop_latch_and_warning():
    """v1 router: more same-shard lanes than the static budget -- the
    excess is dropped with result False, counted, and warned ONCE, never
    silent.  (The v2 adaptive router only drops under an explicit
    ``max_lane_budget`` cap; its drop accounting is pinned in
    tests/test_router_v2.py.)"""
    s = 8
    # 48 distinct keys that all route to one shard; budget will be 32
    keys, k = [], 0
    while len(keys) < 48:
        if int(np_shard_of(np.array([k]), s)[0]) == 3:
            keys.append(k)
        k += 1
    keys = np.array(keys, np.int32)
    m = ShardedDurableMap(SetSpec(capacity=512, mode="soft"), n_shards=s,
                          router="v1")
    assert m.sspec.lane_budget(len(keys)) == 32
    with pytest.warns(RuntimeWarning, match="dropped 16 lane"):
        ok = np.array(m.insert(keys, keys))
    assert ok[:32].all() and not ok[32:].any()   # first-32 lane priority
    assert len(m) == 32 and m.router_dropped == 16
    with warnings.catch_warnings():              # one-shot: no second warning
        warnings.simplefilter("error")
        m.insert(keys[:1])
    assert m.router_dropped == 16                # kept batch routed cleanly
    # the dropped keys were never executed anywhere
    assert not np.array(m.contains(keys[32:])).any()


def test_sharded_stash_overflow_surfaces():
    """The bucket stash-overflow latch propagates through the sharded
    façade: ``overflowed`` flips and a one-shot RuntimeWarning fires."""
    m = ShardedDurableMap(SetSpec(capacity=64, mode="soft", backend="bucket",
                                  n_buckets=1, bucket_width=1, stash_size=1),
                          n_shards=1)
    assert not m.overflowed
    with pytest.warns(RuntimeWarning, match="overflow latched"):
        m.insert(np.arange(1, 8, dtype=np.int32))
    assert m.overflowed


# ---------------------------------------------------------------------------
# Stacked state + parallel recovery.
# ---------------------------------------------------------------------------

def test_make_state_is_stacked_per_shard():
    sspec = ShardSpec(base=SetSpec(capacity=64, backend="bucket"),
                      n_shards=4)
    st = SH.make_state(sspec)
    per = sspec.shard_spec()
    assert st.keys.shape == (4, per.capacity)
    nb, w = per.bucket_geometry()
    assert st.bkeys.shape == (4, nb, w)
    assert st.n_psync.shape == (4,)


@pytest.mark.parametrize("backend", BACKEND_NAMES)
def test_parallel_recovery_with_independent_adversaries(backend):
    m = ShardedDurableMap(SetSpec(capacity=256, mode="soft",
                                  backend=backend), n_shards=8)
    keys = np.arange(100, dtype=np.int32)
    assert np.array(m.insert(keys, keys * 2)).all()
    m.crash_and_recover(seed=123)    # independent uniform u per shard
    # completed SOFT inserts are durable under ANY adversary
    assert np.array(m.contains(keys)).all()
    assert list(np.array(m.get(keys))) == [2 * int(k) for k in keys]
    assert len(m) == 100
    hist = m.last_recovery_hist_shards
    assert hist.shape == (8, 5)
    assert int(hist[:, 3].sum()) == 100        # VALID bin, summed over shards
    np.testing.assert_array_equal(m.last_recovery_hist, hist.sum(axis=0))


def test_sharded_bucket_backend_reaches_pallas_kernels(monkeypatch):
    calls = {"probe": 0, "scan": 0}
    real_probe, real_scan = hp_ops.probe_pallas, rs_ops.scan_pallas

    def probe_wrap(*a, **k):
        calls["probe"] += 1
        return real_probe(*a, **k)

    def scan_wrap(*a, **k):
        calls["scan"] += 1
        return real_scan(*a, **k)

    monkeypatch.setattr(hp_ops, "probe_pallas", probe_wrap)
    monkeypatch.setattr(rs_ops, "scan_pallas", scan_wrap)
    # unique capacity => unique ShardSpec => fresh trace hits the wrappers
    m = ShardedDurableMap(SetSpec(capacity=288, mode="soft",
                                  backend="bucket"), n_shards=4)
    m.insert(np.arange(10))
    assert calls["probe"] >= 1, "probe_pallas not under the vmapped dispatch"
    m.crash_and_recover()
    assert calls["scan"] >= 1, "scan_pallas not under the vmapped recovery"
    assert len(m) == 10


# ---------------------------------------------------------------------------
# Opt-in shard_map path over a multi-device mesh (subprocess: fake devices).
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHARD_MAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax
    from repro.core import ShardedDurableMap, SetSpec
    assert jax.device_count() == 4
    for backend in ("probe", "bucket"):
        a = ShardedDurableMap(SetSpec(capacity=256, backend=backend),
                              n_shards=8, use_shard_map=True)
        b = ShardedDurableMap(SetSpec(capacity=256, backend=backend),
                              n_shards=8)
        keys = np.arange(40, dtype=np.int32)
        np.testing.assert_array_equal(np.array(a.insert(keys, keys * 3)),
                                      np.array(b.insert(keys, keys * 3)))
        np.testing.assert_array_equal(np.array(a.remove(keys[::3])),
                                      np.array(b.remove(keys[::3])))
        np.testing.assert_array_equal(np.array(a.contains(keys)),
                                      np.array(b.contains(keys)))
        a.crash_and_recover(); b.crash_and_recover()
        np.testing.assert_array_equal(np.array(a.contains(keys)),
                                      np.array(b.contains(keys)))
        assert a.psyncs == b.psyncs and len(a) == len(b)
        assert len(a.state.keys.sharding.device_set) == 4, \\
            "state not partitioned over the mesh"
        print(backend, "shard_map OK")
""")


@pytest.mark.slow
def test_shard_map_path_matches_vmap_path():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", SHARD_MAP_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "probe shard_map OK" in r.stdout
    assert "bucket shard_map OK" in r.stdout
