"""Plan/commit pipeline tests (DESIGN.md §2a).

Covers the three refactor invariants:

  * the vectorized commit kernels (``table_claim`` / ``table_release``)
    reproduce the retired sequential writers' lane-order linearization
    bit-for-bit, including on duplicate-heavy and near-full batches
    (randomized sweep always; hypothesis property when available);
  * psync parity across the refactor: SOFT pays exactly 1 psync per
    successful update and 0 per read -- the pre-refactor counter values --
    for all three backends, flat and sharded;
  * the probe backend's Pallas read route (``hp_ops.table_lookup``) agrees
    with the pure-lax windowed lookup and actually reaches the kernel.
"""
import numpy as np
import jax.numpy as jnp
import pytest

import repro.kernels.hash_probe.ops as hp_ops
from repro.core import (DurableMap, ShardedDurableMap, SetSpec, EMPTY, TOMB,
                        OP_CONTAINS, OP_INSERT, OP_REMOVE, OracleSet)
from repro.core import durable_set as DS

BACKEND_NAMES = ("probe", "scan", "bucket")


# ---------------------------------------------------------------------------
# Commit-kernel equivalence: vectorized claim/release == the sequential
# reference linearization, on arbitrary tables and lane mixes.
# ---------------------------------------------------------------------------


def _random_scenario(rng, t=64, b=24, key_range=12, fill=0.0, max_probe=8):
    """A (table, keys, ids, do) quadruple.  ``key_range`` small => heavy
    in-batch duplication (contended probe chains); ``fill`` pre-occupies a
    fraction of slots (near-full tables) with a sprinkle of TOMBs."""
    table = np.full(t, EMPTY, np.int32)
    n_fill = int(t * fill)
    slots = rng.choice(t, n_fill, replace=False)
    table[slots] = rng.integers(1000, 2000, n_fill)
    tombs = slots[rng.random(n_fill) < 0.3]
    table[tombs] = TOMB
    keys = rng.integers(0, key_range, b).astype(np.int32)
    ids = np.arange(b, dtype=np.int32)          # distinct node ids
    do = rng.random(b) < 0.7
    return table, keys, ids, do, max_probe


def _assert_claim_matches_ref(table, keys, ids, do, max_probe):
    ref_t, ref_ovf = DS._table_write_ref(
        jnp.asarray(table), jnp.asarray(keys), jnp.asarray(ids),
        jnp.asarray(do), max_probe)
    vec_t, vec_ovf = DS.table_claim(
        jnp.asarray(table), jnp.asarray(keys), jnp.asarray(ids),
        jnp.asarray(do), max_probe)
    np.testing.assert_array_equal(np.array(ref_t), np.array(vec_t))
    assert bool(ref_ovf) == bool(vec_ovf)


def test_table_claim_matches_ref_randomized_sweep():
    """Deterministic seed sweep spanning empty, duplicate-heavy, near-full
    and overflowing regimes (runs even without hypothesis installed)."""
    rng = np.random.default_rng(0)
    for fill in (0.0, 0.5, 0.9, 0.97):
        for key_range in (3, 12, 1000):        # 3 => almost every lane dups
            for _ in range(8):
                _assert_claim_matches_ref(
                    *_random_scenario(rng, fill=fill, key_range=key_range))


def test_table_claim_matches_ref_all_lanes_one_chain():
    """Worst case: every lane carries the SAME key -- the claim loop must
    serialize the whole batch through the conflict guard, one commit per
    round, and still land every id exactly where the sequential writer
    does."""
    b, t = 16, 64
    keys = np.full(b, 7, np.int32)
    ids = np.arange(b, dtype=np.int32)
    do = np.ones(b, bool)
    _assert_claim_matches_ref(np.full(t, EMPTY, np.int32), keys, ids, do, 32)


def test_table_release_matches_ref_randomized_sweep():
    rng = np.random.default_rng(1)
    for _ in range(16):
        table, keys, ids, do, mp = _random_scenario(rng, fill=0.4)
        # place some lanes' ids for real so deletes have live targets
        table_j, _ = DS._table_write_ref(
            jnp.asarray(table), jnp.asarray(keys), jnp.asarray(ids),
            jnp.asarray(do), mp)
        dele = rng.random(len(keys)) < 0.6
        ref = DS._table_delete_ref(table_j, jnp.asarray(keys),
                                   jnp.asarray(ids), jnp.asarray(dele), mp)
        vec = DS.table_release(table_j, jnp.asarray(keys),
                               jnp.asarray(ids), jnp.asarray(dele), mp)
        np.testing.assert_array_equal(np.array(ref), np.array(vec))


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                               # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1),
           st.sampled_from([2, 4, 40]),           # duplicate-heavy ... spread
           st.floats(0.0, 0.98),                  # near-full tables included
           st.sampled_from([4, 8, 32]))
    def test_property_vectorized_claim_equals_reference(seed, key_range,
                                                        fill, max_probe):
        rng = np.random.default_rng(seed)
        _assert_claim_matches_ref(*_random_scenario(
            rng, t=32, b=16, key_range=key_range, fill=fill,
            max_probe=max_probe))


# ---------------------------------------------------------------------------
# Psync parity across the refactor: the SOFT bound, flat and sharded.
# ---------------------------------------------------------------------------


def _mixed_trace(m, rng, rounds=6, batch=16, key_range=24):
    """Drive ``m`` with mixed batches; return (n_successful_updates,
    n_reads, n_update_lanes)."""
    upd, reads, upd_lanes = 0, 0, 0
    for _ in range(rounds):
        ops = rng.integers(0, 3, batch).astype(np.int32)
        keys = rng.integers(0, key_range, batch).astype(np.int32)
        res = np.array(m.apply(ops, keys, keys * 2))
        is_upd = ops != OP_CONTAINS
        upd += int(res[is_upd].sum())
        upd_lanes += int(is_upd.sum())
        reads += int((~is_upd).sum())
    return upd, reads, upd_lanes


@pytest.mark.parametrize("sharded", (False, True), ids=("flat", "sharded"))
@pytest.mark.parametrize("backend", BACKEND_NAMES)
def test_soft_psync_bound_exact(backend, sharded):
    """SOFT: exactly 1 psync per SUCCESSFUL update and 0 per read -- the
    paper's lower bound and the pre-refactor counter semantics.  Asserted
    lane-exactly from the op results, so any extra (or elided) psync the
    pipeline introduced would shift the counter."""
    spec = SetSpec(capacity=256, mode="soft", backend=backend)
    m = ShardedDurableMap(spec, n_shards=4) if sharded else DurableMap(spec)
    rng = np.random.default_rng(42)
    upd, reads, upd_lanes = _mixed_trace(m, rng)
    assert reads > 0 and upd > 0 and upd < upd_lanes  # trace is non-trivial
    assert m.psyncs == upd, (
        f"SOFT must psync exactly once per successful update: "
        f"{m.psyncs} psyncs vs {upd} successful updates")
    # reads stay free even when issued alone
    before = m.psyncs
    m.contains(np.arange(16))
    m.get(np.arange(16))
    assert m.psyncs == before


@pytest.mark.parametrize("mode", ("soft", "linkfree", "logfree"))
@pytest.mark.parametrize("backend", BACKEND_NAMES)
def test_psync_counter_matches_oracle_trace(backend, mode):
    """Every mode's counter equals the instruction-granularity OracleSet on
    a duplicate-free sequential trace (single-lane batches == the oracle's
    program order), flat and sharded."""
    rng = np.random.default_rng(5)
    flat = DurableMap(SetSpec(capacity=64, mode=mode, backend=backend))
    shrd = ShardedDurableMap(SetSpec(capacity=64, mode=mode,
                                     backend=backend), n_shards=4)
    o = OracleSet(64, mode=mode)
    for _ in range(40):
        op = rng.choice(["insert", "remove", "contains"])
        k = int(rng.integers(0, 16))
        if op == "insert":
            flat.insert([k], [k * 2]); shrd.insert([k], [k * 2])
            o.insert(k, k * 2)
        elif op == "remove":
            flat.remove([k]); shrd.remove([k]); o.remove(k)
        else:
            flat.contains([k]); shrd.contains([k]); o.contains(k)
    assert flat.psyncs == o.psyncs, (backend, mode)
    assert shrd.psyncs == o.psyncs, (backend, mode)


# ---------------------------------------------------------------------------
# Probe backend's Pallas read route.
# ---------------------------------------------------------------------------


def test_probe_pallas_lookup_matches_lax():
    """use_pallas True/False must be observationally identical for the
    probe backend."""
    rng = np.random.default_rng(9)
    probes = rng.integers(0, 80, 32).astype(np.int32)
    keys = np.arange(64, dtype=np.int32)
    out = {}
    for flag in (True, False):
        m = DurableMap(SetSpec(capacity=128, mode="soft", backend="probe",
                               probe_pallas_lookup=flag))
        m.insert(keys, keys * 3)
        m.remove(keys[::4])
        out[flag] = (np.array(m.contains(probes)),
                     np.array(m.get(keys, default=-1)), m.psyncs)
    np.testing.assert_array_equal(out[True][0], out[False][0])
    np.testing.assert_array_equal(out[True][1], out[False][1])
    assert out[True][2] == out[False][2]


def test_probe_backend_reaches_pallas_kernel(monkeypatch):
    calls = {"probe": 0}
    real_probe = hp_ops.probe_pallas

    def probe_wrap(*a, **k):
        calls["probe"] += 1
        return real_probe(*a, **k)

    monkeypatch.setattr(hp_ops, "probe_pallas", probe_wrap)
    # unique capacity => unique SetSpec => fresh jit trace hits the wrapper
    m = DurableMap(SetSpec(capacity=152, mode="soft", backend="probe",
                           probe_pallas_lookup=True))
    m.insert(np.arange(16))                       # 8-aligned batch
    assert calls["probe"] >= 1, "probe_pallas not on the probe lookup path"
    assert list(np.array(m.contains(np.arange(8)))) == [True] * 8


def test_probe_small_batch_falls_back_to_lax(monkeypatch):
    """A batch that is not a whole 128-lane tile no longer falls back to
    the lax lookup: it takes the kernel too, the wrapper pads the queries,
    and the padding never matches."""
    calls = {"probe": 0}
    real_probe = hp_ops.probe_pallas

    def probe_wrap(*a, **k):
        calls["probe"] += 1
        return real_probe(*a, **k)

    monkeypatch.setattr(hp_ops, "probe_pallas", probe_wrap)
    m = DurableMap(SetSpec(capacity=168, mode="soft", backend="probe",
                           probe_pallas_lookup=True))
    m.insert([1, 2, 3])                           # b == 3
    assert list(np.array(m.contains([1, 4, 3]))) == [True, False, True]
    assert calls["probe"] >= 1, "ragged batch left the kernel route"


def test_plan_insert_classification():
    """The shared plan: dedup winners, duplicate losers, found joins."""
    st = DS.make_state(8)
    st, _ = DS._insert_impl(st, jnp.asarray([5]), jnp.asarray([5]),
                            mode="soft", lookup_fn=DS._lookup_scan)
    keys = jnp.asarray([5, 6, 6, 7])
    active = jnp.ones(4, bool)
    plan = DS.plan_insert(st, keys, active, DS._lookup_scan(st, keys))
    assert list(np.array(plan.win)) == [False, True, False, True]
    assert list(np.array(plan.lose_dup)) == [False, False, True, False]
    assert list(np.array(plan.found)) == [True, False, False, False]
    assert int(plan.count) == 2 and not bool(plan.overflow)
    rem = DS.plan_remove(st, keys, active, DS._lookup_scan(st, keys))
    assert list(np.array(rem.win)) == [True, False, False, False]
    assert int(rem.count) == 1
