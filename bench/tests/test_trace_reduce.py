"""The trace reduction: on a hand-made trace whose answers are known, and
on a short trace recorded on one TPU v5e (``data/set_uniform_r90.xplane.pb``:
a ``--trace 1`` run of the cell with a 0.03 s window)."""
import os

import pytest

from bench import trace_reduce
from bench.trace_reduce import Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"
MS = 1_000_000


def _synthetic() -> Trace:
    host = [("bench.window", 0, 10 * MS),
            ("bench.apply", 1 * MS, 4_500_000),
            ("np.asarray(jax.Array)", 2 * MS, 3 * MS),
            ("bench.recover", 6 * MS, 3 * MS)]
    ops = [("%a = s32[8] fusion(s32[8] %x)", 1 * MS, 1 * MS),
           ("%k.1 = s32[8] custom-call(s32[8] %a)", 1_500_000, 1 * MS),
           ("%b = s32[8] fusion(s32[8] %k.1)", 7 * MS, 1 * MS),
           ("%late = s32[8] copy(s32[8] %b)", 9_500_000, 1 * MS)]
    mods = [("jit_step(1)", 1 * MS, 2 * MS), ("jit_recover(2)", 7 * MS,
                                              4 * MS)]
    return Trace([("/host:CPU", [("python", host)]),
                  (DEV, [("XLA Ops", ops), ("XLA Modules", mods)])])


def test_busy_is_the_union_of_ops_inside_the_window():
    t = _synthetic()
    assert t.window_s == pytest.approx(0.010)
    # [1, 2.5] + [7, 8] + [9.5, 10] (the last op is cut at the close)
    assert t.busy_s() == pytest.approx(0.003)


def test_device_seconds_by_name():
    t = _synthetic()
    assert t.device_s(["^%k"]) == pytest.approx(0.001)
    assert t.device_s(["^jit_recover\\("], "modules") == pytest.approx(0.003)
    assert t.device_s(["nothing"]) == 0.0


def test_idle_gaps_are_named_by_what_the_host_did():
    gaps = dict(_synthetic().idle_gaps())
    assert gaps["bench.apply > np.asarray(jax.Array)"] == pytest.approx(
        0.0045)                                   # 2.5 .. 7 ms
    assert gaps["bench.recover"] == pytest.approx(0.0015)    # 8 .. 9.5 ms
    assert gaps["host: outside any event"] == pytest.approx(0.001)
    assert sum(gaps.values()) == pytest.approx(0.007)


def test_top_ops_name_program_and_instruction():
    top = dict(_synthetic().top_ops())
    assert top["step:%k.1:custom-call"] == pytest.approx(0.001)
    assert top["recover:%late:copy"] == pytest.approx(0.0005)


def test_recorded_chip_trace():
    path = os.path.join(DATA, "set_uniform_r90.xplane.pb")
    t = trace_reduce.load(path)
    assert t.devices == [DEV]
    assert 0 < t.busy_s() < t.window_s
    lookup = t.device_s(["^%[^ ]*probe_pallas[^ ]* = "])
    apply = t.device_s(["^jit__apply_v2\\("], "modules")
    assert 0 < lookup < apply <= t.window_s
    assert apply == pytest.approx(t.busy_s(), rel=0.05)   # apply-bound
    b = t.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    idle = sum(s for _, s in t.idle_gaps(k=1000))
    assert idle == pytest.approx(t.window_s - t.busy_s(), rel=1e-6)
