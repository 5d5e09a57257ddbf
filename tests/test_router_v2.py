"""Router v2 conformance suite (DESIGN.md §6).

Pins the three guarantees of the two-stage device-local router:

  1. CONFORMANCE -- for any device-group count D, any placement policy,
     and the adaptive lane budget, Router v2 produces bit-identical
     results, state, and psync counters to the v1 single-stage router on
     randomized mixed-op traces, across all three index backends
     (hypothesis property + deterministic sweep incl. crash/recovery).
  2. NO ALL-GATHER -- on 4 fake CPU devices the compiled per-device
     ``shard_map`` program contains no cross-device collective, and its
     stage-2 sort runs over the device-local sub-batch, not the full
     batch (the v1 program, by contrast, compiles an all-reduce and a
     full-batch sort on every device).
  3. DROP EXACTNESS -- with a deliberately tiny ``max_lane_budget``,
     dropped == lanes over budget, dropped lanes return False with zero
     side effects (state bit-equal to applying only the kept lanes), and
     the one-shot RuntimeWarning fires exactly once.
  4. ONE READ PER BATCH -- the stage-2 program packs results, kept mask,
     dropped count and the per-group overflow latch into one array, so a
     synchronous batch makes exactly one device-to-host read and
     ``force()`` unpacks the same tuples as the v1 router and the drop
     rule give; the latch is reduced per group and ORed on the host.
"""
import contextlib
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (ShardedDurableMap, SetSpec, ShardSpec,
                        OP_CONTAINS, OP_INSERT, OP_NOP, OP_REMOVE)
from repro.core import router as RT
from repro.core import shard as SH

try:        # dev-only dependency: property test degrades to a seeded sweep
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

BACKENDS = ("probe", "scan", "bucket")
_BATCH = 8


def _pair(backend, mode="soft", *, n_shards=8, placement="contiguous",
          groups=0, capacity=128):
    """A (v2, v1) map pair over the same per-shard geometry."""
    base = SetSpec(capacity=capacity, mode=mode, backend=backend)
    v2 = ShardedDurableMap(base, n_shards=n_shards, placement=placement,
                           n_device_groups=groups)
    v1 = ShardedDurableMap(base, n_shards=n_shards, router="v1")
    return v2, v1


def _canonical_state(m):
    """The stacked state re-ordered to GLOBAL shard order (placement only
    permutes the storage rows, so this is the layout-independent view)."""
    rows = RT.np_storage_rows(m.sspec, RT.resolve_groups(m.sspec))
    return jax.tree.map(lambda x: np.asarray(x)[rows], m.state)


def _assert_state_identical(v2, v1):
    a, b = _canonical_state(v2), _canonical_state(v1)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(la, lb)


def _run_trace(v2, v1, trace):
    """Feed the same mixed-op trace (op code, key) through both maps in
    _BATCH-lane batches and assert per-lane result equality."""
    for i in range(0, len(trace), _BATCH):
        chunk = trace[i:i + _BATCH]
        codes = np.full(_BATCH, OP_NOP, np.int32)
        keys = np.zeros(_BATCH, np.int32)
        for j, (code, key) in enumerate(chunk):
            codes[j], keys[j] = code, key
        got2 = np.array(v2.apply(codes, keys, keys * 7))
        got1 = np.array(v1.apply(codes, keys, keys * 7))
        np.testing.assert_array_equal(got2, got1, err_msg=str(chunk))


# ---------------------------------------------------------------------------
# 1. Conformance: v2 == v1 bit-for-bit.
# ---------------------------------------------------------------------------

def _check_bit_identical(backend, placement, groups, trace):
    """Any D, any placement, adaptive budgets --> results, state, and
    psync counters bit-identical to the v1 router."""
    v2, v1 = _pair(backend, placement=placement, groups=groups)
    _run_trace(v2, v1, trace)
    assert v2.psyncs == v1.psyncs
    assert v2.ops == v1.ops
    assert len(v2) == len(v1)
    assert v2.router_dropped == 0            # uncapped adaptive never drops
    _assert_state_identical(v2, v1)
    # per-shard counters agree under the placement row map too
    rows = RT.np_storage_rows(v2.sspec, RT.resolve_groups(v2.sspec))
    np.testing.assert_array_equal(np.asarray(v2.state.n_psync)[rows],
                                  np.asarray(v1.state.n_psync))


if HAVE_HYPOTHESIS:
    trace_strategy = st.lists(
        st.tuples(st.integers(0, 3), st.integers(0, 31)),  # incl. OP_NOP
        min_size=1, max_size=32)

    @settings(max_examples=25, deadline=None)
    @given(backend=st.sampled_from(BACKENDS),
           placement=st.sampled_from(RT.PLACEMENTS),
           groups=st.sampled_from((0, 2, 4, 8)),
           trace=trace_strategy)
    def test_router_v2_bit_identical_to_v1(backend, placement, groups,
                                           trace):
        _check_bit_identical(backend, placement, groups, trace)
else:                                                 # pragma: no cover
    @pytest.mark.parametrize("seed", range(8))
    def test_router_v2_bit_identical_to_v1(seed):
        rng = np.random.default_rng(seed)
        trace = [(int(c), int(k)) for c, k in
                 zip(rng.integers(0, 4, 24), rng.integers(0, 32, 24))]
        _check_bit_identical(BACKENDS[seed % 3], RT.PLACEMENTS[seed % 2],
                             (0, 2, 4, 8)[seed % 4], trace)


@pytest.mark.parametrize("mode", ("soft", "linkfree"))
@pytest.mark.parametrize("placement", RT.PLACEMENTS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_router_v2_conformance_with_recovery(backend, placement, mode):
    """Deterministic sweep: a longer randomized trace with a mid-trace
    crash+recovery; v2 (D=4 logical groups) stays bit-identical to v1
    through the recovery rebuild."""
    rng = np.random.default_rng(7)
    v2, v1 = _pair(backend, mode, placement=placement, groups=4,
                   capacity=256)
    for r in range(6):
        ops = rng.integers(0, 3, 16).astype(np.int32)
        keys = rng.integers(0, 96, 16).astype(np.int32)
        np.testing.assert_array_equal(np.array(v2.apply(ops, keys, keys * 2)),
                                      np.array(v1.apply(ops, keys, keys * 2)))
        if r == 3:
            v2.crash_and_recover(seed=11)
            v1.crash_and_recover(seed=11)
    probe = np.arange(96)
    np.testing.assert_array_equal(np.array(v2.contains(probe)),
                                  np.array(v1.contains(probe)))
    np.testing.assert_array_equal(np.array(v2.get(probe, default=-5)),
                                  np.array(v1.get(probe, default=-5)))
    assert v2.psyncs == v1.psyncs and v2.ops == v1.ops
    _assert_state_identical(v2, v1)


def test_nop_lanes_not_transported_and_budget_neutral():
    """OP_NOP input lanes (caller padding) are exact no-ops: result False,
    never shipped to a device, never counted in the occupancy the
    adaptive budget is sized from."""
    m = ShardedDurableMap(SetSpec(capacity=128), n_shards=4)
    codes = np.array([OP_INSERT, OP_NOP, OP_INSERT, OP_NOP], np.int32)
    keys = np.array([1, 2, 3, 4], np.int32)
    res = np.array(m.apply(codes, keys, keys))
    assert list(res) == [True, False, True, False]
    plan = m.last_route
    assert int(plan.occupancy.sum()) == 2          # real lanes only
    assert (plan.slot[codes == OP_NOP] == -1).all()
    assert len(m) == 2 and m.router_dropped == 0


# ---------------------------------------------------------------------------
# 2. Placement + budget unit rules.
# ---------------------------------------------------------------------------


def test_storage_rows_policies():
    sp_c = ShardSpec(base=SetSpec(capacity=64), n_shards=8)
    np.testing.assert_array_equal(RT.np_storage_rows(sp_c, 4), np.arange(8))
    sp_s = ShardSpec(base=SetSpec(capacity=64), n_shards=8,
                     placement="strided")
    # device d of 4 owns global shards {d, d+4}: row = (sid%4)*2 + sid//4
    np.testing.assert_array_equal(RT.np_storage_rows(sp_s, 4),
                                  [0, 2, 4, 6, 1, 3, 5, 7])
    # a placement is a permutation for every D
    for d in (1, 2, 4, 8):
        rows = RT.np_storage_rows(sp_s, d)
        assert sorted(rows) == list(range(8))
    # host and in-jit row math agree
    keys = np.arange(512, dtype=np.int32)
    for sp, d in ((sp_c, 4), (sp_s, 4), (sp_s, 2)):
        host = RT._np_row_of(keys, sp, d)
        per = sp.n_shards // d
        gid = host // per
        local = np.array(RT._local_row(jnp.asarray(keys), sp, d))
        np.testing.assert_array_equal(local, host - gid * per)


def test_adaptive_budget_rules():
    sp = ShardSpec(base=SetSpec(capacity=1024), n_shards=8)
    assert RT.adaptive_lane_budget(sp, 1024, 100) == 128
    assert RT.adaptive_lane_budget(sp, 1024, 128) == 128   # exact pow2
    assert RT.adaptive_lane_budget(sp, 1024, 129) == 256
    assert RT.adaptive_lane_budget(sp, 1024, 3) == 32      # min clamp
    assert RT.adaptive_lane_budget(sp, 16, 3) == 16        # tiny batch
    assert RT.adaptive_lane_budget(sp, 1024, 2000) == 1024  # never > B
    capped = ShardSpec(base=SetSpec(capacity=1024), n_shards=8,
                       max_lane_budget=64)
    assert RT.adaptive_lane_budget(capped, 1024, 500) == 64
    s1 = ShardSpec(base=SetSpec(capacity=1024), n_shards=1)
    assert RT.adaptive_lane_budget(s1, 1024, 7) == 1024    # identity routing
    assert RT.budget_candidates(sp, 1024) == (32, 64, 128, 256, 512, 1024)
    assert RT.budget_candidates(capped, 1024) == (32, 64)


def test_shard_spec_v2_validation():
    base = SetSpec(capacity=64)
    with pytest.raises(ValueError, match="router"):
        ShardSpec(base=base, router="v3")
    with pytest.raises(ValueError, match="placement"):
        ShardSpec(base=base, placement="random")
    with pytest.raises(ValueError, match="max_lane_budget"):
        ShardSpec(base=base, max_lane_budget=-1)
    with pytest.raises(ValueError, match="n_device_groups"):
        ShardSpec(base=base, n_device_groups=3)
    with pytest.raises(ValueError, match="n_device_groups"):
        ShardSpec(base=base, n_shards=4, n_device_groups=8)


def test_precompile_covers_budget_set_and_is_a_noop():
    m = ShardedDurableMap(SetSpec(capacity=1024), n_shards=8)
    m.insert([1, 2, 3])
    p0, o0, n0 = m.psyncs, m.ops, len(m)
    before = _canonical_state(m)
    budgets = m.precompile(256)
    assert budgets == RT.budget_candidates(m.sspec, 256) == (32, 64, 128,
                                                             256)
    assert (m.psyncs, m.ops, len(m)) == (p0, o0, n0)
    for la, lb in zip(jax.tree.leaves(before),
                      jax.tree.leaves(_canonical_state(m))):
        np.testing.assert_array_equal(la, lb)


# ---------------------------------------------------------------------------
# 3. Drop accounting exactness under a deliberate budget cap.
# ---------------------------------------------------------------------------


def _kept_mask(keys, ops, sspec, budget):
    """Host oracle for the drop rule: per shard, the first ``budget``
    real lanes in batch order are kept."""
    rows = RT._np_row_of(np.asarray(keys, np.int32), sspec,
                         RT.resolve_groups(sspec))
    seen = {}
    keep = np.zeros(len(keys), bool)
    for i, (r, op) in enumerate(zip(rows, ops)):
        if op == OP_NOP:
            continue
        seen[r] = seen.get(r, 0) + 1
        keep[i] = seen[r] <= budget
    return keep


@pytest.mark.parametrize("backend", BACKENDS)
def test_drop_accounting_exact(backend):
    """Tiny max_lane_budget: dropped count == lanes over budget, dropped
    lanes return False with ZERO side effects (state bit-equal to a run
    of only the kept lanes), and the RuntimeWarning is one-shot."""
    budget = 2
    spec = SetSpec(capacity=512, backend=backend)
    m = ShardedDurableMap(spec, n_shards=8, max_lane_budget=budget,
                          min_lane_budget=1)
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 10_000, 64).astype(np.int32)
    ops = np.full(64, OP_INSERT, np.int32)
    keep = _kept_mask(keys, ops, m.sspec, budget)

    with pytest.warns(RuntimeWarning, match="dropped"):
        got = np.array(m.insert(keys, keys * 3))
    occ = m.last_route.occupancy
    assert m.last_route.lane_budget == budget
    expected_drops = int(np.maximum(occ - budget, 0).sum())
    assert expected_drops > 0, "test geometry must actually drop"
    assert m.router_dropped == expected_drops == int((~keep).sum())
    assert not got[~keep].any(), "dropped lanes must return False"

    # zero side effects: bit-equal to executing only the kept lanes
    ref = ShardedDurableMap(spec, n_shards=8, max_lane_budget=budget,
                            min_lane_budget=1)
    ref_got = np.array(ref.insert(keys[keep], keys[keep] * 3))
    np.testing.assert_array_equal(got[keep], ref_got)
    assert m.psyncs == ref.psyncs and len(m) == len(ref)
    _assert_state_identical(m, ref)
    assert not np.array(m.contains(keys[~keep])).any()

    # one-shot warning: the second dropping batch stays silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        m.insert(keys)
    assert m.router_dropped > expected_drops   # still counted, not warned


# ---------------------------------------------------------------------------
# 4. The no-all-gather guarantee (4 fake CPU devices, compiled HLO).
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NO_COLLECTIVE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import re
    import jax, jax.numpy as jnp
    from repro.core import SetSpec, ShardSpec
    from repro.core import shard as SH
    from repro.core import router as RT
    assert jax.device_count() == 4

    COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
                   "all-to-all")

    def sort_sizes(hlo):
        return {int(s) for s in
                re.findall(r"sort[^=]*= \\(?[a-z0-9]+\\[(\\d+)", hlo)}

    base = SetSpec(capacity=256, backend="bucket")
    # v2: the per-device program routes ONLY its own (Bd,) lanes
    sspec = ShardSpec(base=base, n_shards=8, use_shard_map=True)
    assert RT.resolve_groups(sspec) == 4
    D, Bd, L = 4, 32, 16
    z = jnp.zeros((D, Bd), jnp.int32)
    hlo = RT._apply_v2.lower(SH.make_state(sspec), z, z, z, sspec=sspec,
                             groups=D, lane_budget=L).compile().as_text()
    found = [c for c in COLLECTIVES if c in hlo]
    assert not found, f"v2 routed dispatch compiled collectives: {found}"
    assert sort_sizes(hlo) <= {Bd}, (
        f"v2 must sort only device-local lanes, saw {sort_sizes(hlo)}")

    # get path too
    act = jnp.ones((D, Bd), bool)
    hlo_g = RT._get_v2.lower(SH.make_state(sspec), z, act, sspec=sspec,
                             groups=D, lane_budget=L,
                             default=0).compile().as_text()
    found = [c for c in COLLECTIVES if c in hlo_g]
    assert not found, f"v2 get compiled collectives: {found}"

    # contrast: the v1 single-stage router DOES communicate -- it
    # materializes and sorts the full batch on every device
    v1 = ShardSpec(base=base, n_shards=8, use_shard_map=True, router="v1")
    B = 128
    zb = jnp.zeros((B,), jnp.int32)
    hlo1 = SH.apply_batch.lower(SH.make_state(v1), zb, zb, zb,
                                sspec=v1).compile().as_text()
    assert any(c in hlo1 for c in COLLECTIVES) or B in sort_sizes(hlo1), \\
        "expected the v1 program to touch the full batch per device"
    print("NO_COLLECTIVE OK")
""")


@pytest.mark.slow
def test_shard_map_program_has_no_collectives():
    """The compiled per-device shard_map program of Router v2 contains no
    cross-device collective on the routed lane grid, and only sorts
    device-local sub-batches (the no-all-gather guarantee)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", NO_COLLECTIVE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "NO_COLLECTIVE OK" in r.stdout


# ---------------------------------------------------------------------------
# 5. One device-to-host read per batch: the packed stage-2 output.
# ---------------------------------------------------------------------------


class _Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``, which every
    ``repro.obs.span`` opens: keeps the name of each span opened."""

    def __init__(self):
        self.names = []

    def __call__(self, name):
        self.names.append(name)
        return contextlib.nullcontext()

    def syncs(self):
        return [n for n in self.names if n.startswith("registry.sync.")]


@pytest.fixture
def spans(monkeypatch):
    rec = _Spans()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    return rec


def _keys_on_row(sspec, row, n):
    """``n`` distinct keys whose storage row is ``row``."""
    cand = np.arange(1, 1 << 14, dtype=np.int32)
    got = cand[RT._np_row_of(cand, sspec, RT.resolve_groups(sspec)) == row]
    return got[:n]


@pytest.mark.parametrize("cap", (0, 2))
@pytest.mark.parametrize("groups", (1, 2, 4))
@pytest.mark.parametrize("backend", ("bucket", "probe"))
def test_packed_read_matches_v1_and_kept_mask(backend, groups, cap, spans):
    """``force()`` unpacks the one packed read into the same tuples as
    before: results equal the v1 router's on the kept lanes, the drop
    mask is exactly the cap's shed set, and every synchronous batch --
    forced by hand or through the façade, apply or get -- opens exactly
    one registry sync span (no overflow read while none has latched)."""
    base = SetSpec(capacity=512, backend=backend)
    kw = dict(max_lane_budget=cap, min_lane_budget=1) if cap else {}
    m = ShardedDurableMap(base, n_shards=8, n_device_groups=groups, **kw)
    v1 = ShardedDurableMap(base, n_shards=8, router="v1")
    assert RT.resolve_groups(m.sspec) == groups
    rng = np.random.default_rng(3 * groups + cap)
    shed = 0
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)   # the drop warning
        for _ in range(3):
            ops = rng.integers(0, 4, 32).astype(np.int32)  # incl. OP_NOP
            keys = rng.integers(0, 64, 32).astype(np.int32)
            plan = RT.host_route(m.sspec, ops, keys, keys * 3)
            keep = _kept_mask(keys, ops, m.sspec, plan.lane_budget) \
                | (ops == OP_NOP)                      # padding: kept
            spans.names.clear()
            m.state, fl = RT.dispatch_plan(m.state, plan, sspec=m.sspec)
            res, dropped, drop_mask = fl.force()
            assert spans.syncs() == ["registry.sync.batch"]
            assert res.dtype == drop_mask.dtype == bool
            np.testing.assert_array_equal(drop_mask, ~keep)
            assert dropped == int((~keep).sum())
            want = v1.apply(np.where(keep, ops, OP_NOP), keys, keys * 3)
            np.testing.assert_array_equal(res, np.array(want))
            assert fl.overflow is False
            shed += dropped

            ops = rng.integers(0, 3, 32).astype(np.int32)
            keep = _kept_mask(keys, ops, m.sspec, adaptive_budget(m, keys))
            spans.names.clear()
            got = np.array(m.apply(ops, keys, keys * 5))
            assert spans.syncs() == ["registry.sync.batch"]
            assert not m._overflow_warned
            np.testing.assert_array_equal(m.last_drop_mask, ~keep)
            np.testing.assert_array_equal(
                got, np.array(v1.apply(np.where(keep, ops, OP_NOP), keys,
                                       keys * 5)))

            spans.names.clear()
            vals = np.array(m.get(keys, default=-1))
            assert spans.syncs() == ["registry.sync.batch"]
            keep = ~m.last_drop_mask
            np.testing.assert_array_equal(
                keep, _kept_mask(keys, np.full(32, OP_CONTAINS), m.sspec,
                                 adaptive_budget(m, keys)))
            np.testing.assert_array_equal(
                vals, np.where(keep, np.array(v1.get(keys, default=-1)),
                               -1))
    assert (shed > 0) == bool(cap), "the cap must shed lanes, and only it"
    assert v1.router_dropped == 0 and m.psyncs == v1.psyncs


def adaptive_budget(m, keys):
    """The lane budget stage 1 picks for an all-real batch of ``keys``."""
    rows = RT._np_row_of(keys, m.sspec, RT.resolve_groups(m.sspec))
    return RT.adaptive_lane_budget(m.sspec, keys.size,
                                   int(np.bincount(rows).max()))


@pytest.mark.parametrize("backend", ("bucket", "probe"))
def test_packed_overflow_latch_and_one_shot_warning(backend, spans):
    """``InFlight.overflow`` is the map's latch after the batch.  The
    façade takes it from the forced batch instead of reading the latch
    again, and its one-shot warning fires on the batch that latched."""
    spec = SetSpec(capacity=64, backend=backend)        # 8 slots a shard
    probe = ShardedDurableMap(spec, n_shards=8, n_device_groups=4)
    m = ShardedDurableMap(spec, n_shards=8, n_device_groups=4)
    keys = _keys_on_row(m.sspec, 5, 16)
    warned_at, latched_at = [], None
    for i in range(4):
        batch = keys[4 * i:4 * i + 4]
        ops = np.full(4, OP_INSERT, np.int32)
        probe.state, fl = RT.apply_batch_v2_async(probe.state, ops, batch,
                                                  batch, sspec=probe.sspec)
        fl.force()
        assert fl.overflow == bool(np.asarray(probe.state.overflow).any())
        spans.names.clear()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            m.insert(batch)
        assert spans.syncs() == ["registry.sync.batch"]
        warned_at += [i for x in w if "overflow latched" in str(x.message)]
        if latched_at is None and np.asarray(m.state.overflow).any():
            latched_at = i
        assert fl.overflow == (latched_at is not None)
    assert latched_at == 2 and warned_at == [latched_at]
    assert m.overflowed and m._overflow_warned


@pytest.mark.parametrize("backend", ("bucket", "probe"))
def test_pipelined_overflow_latch_needs_no_read(backend, spans):
    """The pipelined path takes the latch from each batch it forces: the
    warning fires once, when the batch that latched is forced, and
    neither the forces nor ``pipeline_flush`` read the latch again."""
    m = ShardedDurableMap(SetSpec(capacity=64, backend=backend),
                          n_shards=8, n_device_groups=4, pipeline_depth=2)
    keys = _keys_on_row(m.sspec, 5, 16)
    spans.names.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for i in range(4):
            m.insert(keys[4 * i:4 * i + 4])
        m.pipeline_flush()
    assert sum("overflow latched" in str(x.message) for x in w) == 1
    assert spans.syncs() == ["registry.sync.batch"] * 4
    assert m._overflow_warned and m.overflowed


def _check_one_group_latch(use_shard_map: bool):
    """Only shard row 5 (group 2 of 4) latches; every later batch's
    packed output carries group 2's latch alone, even a batch whose lanes
    all go to group 0, and the host ORs it into ``InFlight.overflow``."""
    m = ShardedDurableMap(SetSpec(capacity=64, backend="probe"),
                          n_shards=8, use_shard_map=use_shard_map,
                          n_device_groups=0 if use_shard_map else 4)
    assert RT.resolve_groups(m.sspec) == 4
    assert RT._use_mesh(m.sspec, 4) == use_shard_map
    with pytest.warns(RuntimeWarning, match="overflow latched"):
        m.insert(_keys_on_row(m.sspec, 5, 12))           # 8 slots a shard
    np.testing.assert_array_equal(np.asarray(m.state.overflow),
                                  [0, 0, 0, 0, 0, 1, 0, 0])
    keys = _keys_on_row(m.sspec, 0, 4)
    ops = np.full(4, OP_CONTAINS, np.int32)
    plan = RT.host_route(m.sspec, ops, keys, keys)
    m.state, packed = RT._apply_v2(
        m.state, jnp.asarray(plan.d_ops), jnp.asarray(plan.d_keys),
        jnp.asarray(plan.d_vals), sspec=m.sspec, groups=plan.groups,
        lane_budget=plan.lane_budget)
    packed = np.asarray(packed)
    assert packed.shape == (4, plan.d_ops.shape[1] + 2)
    np.testing.assert_array_equal(packed[:, -1], [0, 0, 1, 0])
    np.testing.assert_array_equal(packed[:, -2], 0)           # no drops
    RT.release_plan(plan)
    m.state, fl = RT.apply_batch_v2_async(m.state, ops, keys, keys,
                                          sspec=m.sspec)
    fl.force()
    assert fl.overflow is True


ONE_GROUP_LATCH_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    assert jax.device_count() == 4
    sys.path.insert(0, sys.argv[1])
    from test_router_v2 import _check_one_group_latch
    _check_one_group_latch(use_shard_map=True)
    print("ONE_GROUP_LATCH OK")
""")


@pytest.mark.parametrize("path", ("vmap", "shard_map"))
def test_overflow_latch_confined_to_one_group(path):
    """The per-group overflow latch, ORed on the host: one group's shard
    latches, on the vmap path and on a real 4-device ``shard_map`` mesh
    (4 fake CPU devices, in a fresh process)."""
    if path == "vmap":
        _check_one_group_latch(use_shard_map=False)
        return
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", ONE_GROUP_LATCH_SCRIPT,
                        os.path.join(REPO, "tests")], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "ONE_GROUP_LATCH OK" in r.stdout
