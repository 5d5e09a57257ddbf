"""Durable MPMC ring-queue battery (repro.core.queue, DESIGN.md §7).

Covers the tentpole acceptance surface: OracleQueue FIFO trace
conformance, the per-lane crash adversary (no acknowledged enqueue lost,
no committed dequeue resurrected), exact SOFT psync accounting (1 per
successful op, 0 per failed/empty op, 0 during recovery), head/tail
reconstruction from persisted stages alone, and the per-structure
overflow-warning fix.
"""
import warnings

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:     # fine-grained guard: only @given tests skip, the
    # deterministic drivers below still run without the dev dependency
    def settings(**kw):
        return lambda fn: fn

    def given(**kw):
        return lambda fn: pytest.mark.skip(
            reason="dev-only dependency; pip install -r "
                   "requirements-dev.txt")(fn)

    class _StrategyStub:
        def __getattr__(self, name):
            return lambda *a, **k: None
    st = _StrategyStub()

import jax.numpy as jnp

from repro.core import (DurableMap, DurableQueue, OracleQueue, QueueSpec,
                        SetSpec, MODES, VALID, DELETED)
from repro.core import queue as Q


# ---------------------------------------------------------------------------
# Spec + basics
# ---------------------------------------------------------------------------


def test_spec_validation():
    with pytest.raises(ValueError):
        QueueSpec(capacity=12)            # not a power of two
    with pytest.raises(ValueError):
        QueueSpec(capacity=0)
    with pytest.raises(ValueError):
        QueueSpec(capacity=8, mode="nope")
    assert QueueSpec(capacity=8).psync_per_success() == 1
    assert QueueSpec(capacity=8, mode="logfree").psync_per_success() == 2


def test_fifo_basic():
    q = DurableQueue(QueueSpec(capacity=8))
    assert np.asarray(q.enqueue([10, 20, 30])).all()
    assert len(q) == 3
    vals, ok = q.dequeue(2)
    np.testing.assert_array_equal(vals, [10, 20])
    assert ok.all() and len(q) == 1
    vals, ok = q.dequeue(3, default=-1)
    np.testing.assert_array_equal(vals, [30, -1, -1])
    np.testing.assert_array_equal(ok, [True, False, False])
    assert len(q) == 0


def test_full_enqueue_fails_and_empty_dequeue_fails():
    q = DurableQueue(QueueSpec(capacity=4))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        ok = np.asarray(q.enqueue(np.arange(6, dtype=np.int32)))
    np.testing.assert_array_equal(ok, [True] * 4 + [False] * 2)
    assert len(q) == 4 and q.overflowed
    q2 = DurableQueue(QueueSpec(capacity=4))
    _, ok = q2.dequeue(2)
    assert not ok.any() and not q2.overflowed     # empty != overflow


def test_wraparound_recycles_slots():
    """Ticket t lives in slot t & (N-1); many rounds through a tiny ring
    must keep FIFO order and the stage machine consistent."""
    q = DurableQueue(QueueSpec(capacity=4))
    expect = []
    nxt = 0
    rng = np.random.default_rng(3)
    for _ in range(40):
        k = int(rng.integers(1, 4))
        if rng.random() < 0.5 and len(expect) + k <= 4:
            vs = list(range(nxt, nxt + k))
            nxt += k
            assert np.asarray(q.enqueue(np.array(vs, np.int32))).all()
            expect += vs
        else:
            vals, ok = q.dequeue(k)
            got = [int(v) for v, o in zip(vals, ok) if o]
            assert got == expect[:len(got)]
            expect = expect[len(got):]
        assert len(q) == len(expect)
    assert not q.overflowed


def test_active_mask_lanes_are_exact_noops():
    spec = QueueSpec(capacity=8)
    state = Q.make_state(spec)
    active = jnp.asarray([True, False, True, False])
    state, ok, tk = Q.enqueue_impl(state, jnp.arange(4, dtype=jnp.int32),
                                   spec=spec, active=active)
    np.testing.assert_array_equal(np.asarray(ok), [True, False, True, False])
    np.testing.assert_array_equal(np.asarray(tk), [0, -1, 1, -1])
    assert int(Q.size(state)) == 2
    assert int(state.n_psync) == 2            # inactive lanes pay nothing
    assert int(state.n_ops) == 2
    state, vals, ok, _ = Q.dequeue_impl(
        state, jnp.asarray([False, True, True, True]), spec=spec)
    np.testing.assert_array_equal(np.asarray(vals), [0, 0, 2, 0])
    np.testing.assert_array_equal(np.asarray(ok), [False, True, True, False])


def test_peek_is_pure():
    q = DurableQueue(QueueSpec(capacity=8))
    q.enqueue([5, 6])
    p0, o0 = int(q.state.n_psync), int(q.state.n_ops)
    vals, ok = q.peek(4)
    np.testing.assert_array_equal(vals[:2], [5, 6])
    np.testing.assert_array_equal(ok, [True, True, False, False])
    assert (int(q.state.n_psync), int(q.state.n_ops)) == (p0, o0)
    assert len(q) == 2                        # nothing consumed


# ---------------------------------------------------------------------------
# Exact psync accounting (the SOFT bound; satellite)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_psync_exact_per_successful_op(mode):
    """Exactly psync_per_success per successful enqueue/dequeue, 0 for
    full-enqueue/empty-dequeue, 0 during recovery -- flat across the
    whole trace, mirroring the SOFT parity assertions of
    tests/test_durability_property.py."""
    spec = QueueSpec(capacity=8, mode=mode)
    per = spec.psync_per_success()
    q = DurableQueue(spec)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        ok = np.asarray(q.enqueue(np.arange(12, dtype=np.int32)))
    succ = int(ok.sum())
    assert succ == 8 and q.psyncs == per * succ
    _, dok = q.dequeue(12)                    # 8 succeed, 4 empty-fail
    succ += int(np.asarray(dok).sum())
    assert q.psyncs == per * succ
    _, dok = q.dequeue(3)                     # all empty: zero psync
    assert not np.asarray(dok).any() and q.psyncs == per * succ
    assert q.ops == 12 + 12 + 3


@pytest.mark.parametrize("mode", MODES)
def test_recovery_issues_zero_psyncs_and_psyncs_stay_flat(mode):
    """The cumulative psync count across crash/recover cycles equals the
    per-success bound exactly: recovery itself contributes ZERO."""
    spec = QueueSpec(capacity=16, mode=mode)
    per = spec.psync_per_success()
    q = DurableQueue(spec)
    rng = np.random.default_rng(11)
    total_psyncs = 0
    total_succ = 0
    live = 0
    for round_ in range(6):
        vs = rng.integers(0, 100, 5).astype(np.int32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            ok = np.asarray(q.enqueue(vs))
        total_succ += int(ok.sum())
        live += int(ok.sum())
        _, dok = q.dequeue(int(rng.integers(1, 5)))
        total_succ += int(np.asarray(dok).sum())
        live -= int(np.asarray(dok).sum())
        total_psyncs += q.psyncs              # counter resets at recovery
        q.crash_and_recover(u=rng.random(16).astype(np.float32))
        assert q.psyncs == 0, "recovery must issue no psync"
        assert len(q) == live
    assert total_psyncs == per * total_succ


# ---------------------------------------------------------------------------
# Oracle trace conformance (same pattern as the OracleSet battery)
# ---------------------------------------------------------------------------


def _drive_pair(q, o, trace, batch=4):
    """Run a trace through the batched queue and the sequential oracle.
    ``trace``: list of ("enqueue", values) | ("dequeue", n).  Batched
    lanes linearize in lane order, so feeding the oracle element-by-
    element in lane order is the reference semantics."""
    for kind, arg in trace:
        if kind == "enqueue":
            vs = np.asarray(arg, np.int32)
            got = np.asarray(q.enqueue(vs))
            exp = np.array([o.enqueue(int(v)) for v in vs], bool)
            np.testing.assert_array_equal(got, exp, err_msg=str((kind, arg)))
        else:
            vals, ok = q.dequeue(arg, default=-1)
            exp = [o.dequeue() for _ in range(arg)]
            np.testing.assert_array_equal(
                ok, [e[0] for e in exp], err_msg=str((kind, arg)))
            np.testing.assert_array_equal(
                vals, [(-1 if e[1] is None else e[1]) for e in exp],
                err_msg=str((kind, arg)))


@pytest.mark.parametrize("mode", MODES)
def test_oracle_trace_conformance(mode):
    """Random mixed traces: per-lane results AND the psync counter match
    the sequential OracleQueue exactly (every mode -- the queue has no
    read-side helping, so parity is exact beyond soft)."""
    rng = np.random.default_rng(7)
    for seed in range(5):
        q = DurableQueue(QueueSpec(capacity=16, mode=mode))
        o = OracleQueue(16, mode=mode)
        trace = []
        for _ in range(12):
            if rng.random() < 0.55:
                trace.append(("enqueue",
                              rng.integers(0, 99, rng.integers(1, 6))))
            else:
                trace.append(("dequeue", int(rng.integers(1, 6))))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            _drive_pair(q, o, trace)
        assert q.psyncs == o.psyncs, (mode, seed)
        assert len(q) == o.tail - o.head


# ---------------------------------------------------------------------------
# Crash adversary + recovery
# ---------------------------------------------------------------------------


def test_recovery_rebuilds_head_tail_from_stages_alone():
    q = DurableQueue(QueueSpec(capacity=8))
    q.enqueue([1, 2, 3, 4, 5])
    q.dequeue(2)
    h, t = int(q.state.head), int(q.state.tail)
    q.crash_and_recover()
    assert (int(q.state.head), int(q.state.tail)) == (h, t)
    vals, ok = q.dequeue(3)
    np.testing.assert_array_equal(vals[ok], [3, 4, 5])


@pytest.mark.parametrize("mode", MODES)
def test_per_lane_crash_adversary(mode):
    """The per-slot eviction adversary (u in [0,1) per lane of the ring)
    can never lose an acknowledged enqueue nor resurrect a committed
    dequeue: every completed op psyncs before returning, so recovered
    contents are EXACTLY the live FIFO at the crash point."""
    rng = np.random.default_rng(23)
    for trial in range(8):
        q = DurableQueue(QueueSpec(capacity=16, mode=mode))
        expect = []
        nxt = 0
        for _ in range(int(rng.integers(1, 8))):
            if rng.random() < 0.6:
                k = int(rng.integers(1, 6))
                vs = np.arange(nxt, nxt + k, dtype=np.int32)
                nxt += k
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", RuntimeWarning)
                    ok = np.asarray(q.enqueue(vs))
                expect += [int(v) for v, o in zip(vs, ok) if o]
            else:
                _, ok = q.dequeue(int(rng.integers(1, 6)))
                expect = expect[int(np.asarray(ok).sum()):]
        q.crash_and_recover(u=rng.random(16).astype(np.float32))
        assert not q.overflowed, "recovery found a FIFO hole"
        assert len(q) == len(expect)
        vals, ok = q.dequeue(16)
        got = [int(v) for v, o in zip(vals, ok) if o]
        assert got == expect, (mode, trial)


def test_recovery_latches_fifo_hole():
    """A persisted image with a hole in the live ticket range (impossible
    under the batched FIFO discipline, a corruption if it ever appears)
    must latch ``overflow`` instead of recovering silently."""
    spec = QueueSpec(capacity=8)
    persisted = np.zeros(8, np.int32)
    tickets = np.arange(8, dtype=np.int32)
    persisted[5], persisted[7], persisted[6] = VALID, VALID, DELETED
    state, _ = Q.recover(jnp.asarray(persisted), jnp.asarray(tickets),
                         jnp.asarray(tickets * 10), spec=spec)
    assert bool(state.overflow)
    clean = persisted.copy()
    clean[6] = VALID
    state, _ = Q.recover(jnp.asarray(clean), jnp.asarray(tickets),
                         jnp.asarray(tickets * 10), spec=spec)
    assert not bool(state.overflow)
    assert (int(state.head), int(state.tail)) == (5, 8)


def test_recovery_pallas_matches_ref():
    spec_p = QueueSpec(capacity=128, use_pallas=True)
    spec_r = QueueSpec(capacity=128, use_pallas=False)
    q = DurableQueue(spec_p)
    q.enqueue(np.arange(100, dtype=np.int32))
    q.dequeue(37)
    img = Q.crash(q.state, jnp.zeros(128, jnp.float32))
    sp, hp = Q.recover(*img, spec=spec_p)
    sr, hr = Q.recover(*img, spec=spec_r)
    np.testing.assert_array_equal(np.asarray(hp), np.asarray(hr))
    for a, b in zip(sp, sr):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Hypothesis properties (instruction-granularity adversary on the oracle,
# batch-boundary adversary on the JAX queue)
# ---------------------------------------------------------------------------

ops_strategy = st.lists(
    st.tuples(st.sampled_from(["enqueue", "dequeue"]), st.integers(0, 99)),
    min_size=1, max_size=24)


@settings(max_examples=200, deadline=None)
@given(mode=st.sampled_from(MODES), ops=ops_strategy,
       crash_budget=st.integers(0, 120),
       evictions=st.lists(st.integers(0, 6), min_size=8, max_size=8))
def test_oracle_durable_linearizability(mode, ops, crash_budget, evictions):
    """The adversary picks the trace, an event budget landing the crash
    inside an op, and the per-slot eviction bias; recovered FIFO contents
    must be a crash-consistent cut (the single pending op ambiguous)."""
    o = OracleQueue(8, mode=mode)
    left = crash_budget
    for kind, val in ops:
        before = o.events
        res = (o.enqueue(val, budget=max(left, 0)) if kind == "enqueue"
               else o.dequeue(budget=max(left, 0)))
        left -= (o.events - before) + (1 if res is None else 0)
        if res is None:          # crash hit inside this op
            break
    contents, head, tail = OracleQueue.recover(o.crash(list(evictions)))
    ok, msg = o.check_recovery(contents)
    assert ok, msg
    assert tail - head == len(contents)       # no FIFO hole in any cut


@settings(max_examples=50, deadline=None)
@given(ops=ops_strategy, u=st.lists(st.floats(0.0, 0.999), min_size=16,
                                    max_size=16))
def test_jax_queue_matches_oracle_through_crash(ops, u):
    """Batched trace + batch-boundary crash: the JAX queue and the oracle
    agree on results, psyncs, and the recovered FIFO."""
    q = DurableQueue(QueueSpec(capacity=16))
    o = OracleQueue(16)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for kind, val in ops:
            if kind == "enqueue":
                got = bool(np.asarray(q.enqueue([val]))[0])
                assert got == o.enqueue(val)
            else:
                vals, okk = q.dequeue(1, default=-1)
                eok, ev = o.dequeue()
                assert bool(okk[0]) == eok
                assert int(vals[0]) == (-1 if ev is None else ev)
    assert q.psyncs == o.psyncs
    q.crash_and_recover(u=np.asarray(u, np.float32))
    contents, head, tail = OracleQueue.recover(
        o.crash([10] * 16))          # all completed: eviction bias moot
    assert q.psyncs == 0
    assert (int(q.state.head), int(q.state.tail)) == (head, tail)
    vals, okk = q.dequeue(16)
    assert [int(v) for v, k in zip(vals, okk) if k] == contents


# ---------------------------------------------------------------------------
# Per-structure overflow warnings (satellite: the one-shot pattern must
# not be module-global)
# ---------------------------------------------------------------------------


def test_overflow_warning_fires_per_structure_same_spec():
    """Two same-spec maps overflowing in one process must BOTH warn: the
    default-filter ``__warningregistry__`` dedup (message+lineno, module-
    global) used to swallow the second structure's first overflow."""
    spec = SetSpec(capacity=2, backend="probe")
    keys = np.arange(4, dtype=np.int32)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("default")      # the swallowing environment
        a, b = DurableMap(spec), DurableMap(spec)
        a.insert(keys)
        b.insert(keys)
    msgs = [w for w in rec if issubclass(w.category, RuntimeWarning)
            and "overflow" in str(w.message)]
    assert len(msgs) == 2, [str(w.message) for w in rec]


def test_queue_full_and_map_overflow_both_warn():
    """A queue-full warning and a map-overflow warning in the same
    process both fire exactly once per structure."""
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("default")
        m = DurableMap(SetSpec(capacity=2, backend="probe"))
        m.insert(np.arange(4, dtype=np.int32))
        q = DurableQueue(QueueSpec(capacity=2))
        q.enqueue(np.arange(4, dtype=np.int32))
        q.enqueue(np.arange(4, dtype=np.int32))   # latched: no second warn
    runtime = [str(w.message) for w in rec
               if issubclass(w.category, RuntimeWarning)]
    assert len(runtime) == 2, runtime
    assert any("overflow" in m_ for m_ in runtime)
    assert any("DurableQueue full" in m_ for m_ in runtime)
