"""The reducers over the program's own spans (``repro.obs.span``): on a
hand-made trace whose answers are known (nested spans, a span cut by
each edge of the window, an idle gap only partly covered, a device plane
recorded early against its dispatches), and on the recorded chip trace
of a program that has no such spans, where each gives no number."""
import os

import pytest

from bench import trace_reduce
from bench.reducers import (host_span_per_unit, idle_under_span,
                            span_count_per_unit)
from bench.trace_reduce import Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"
MS = 1_000_000
OUT = {"counts": {"batches": 2}}


def _synthetic(devices=(DEV,)) -> Trace:
    host = [("registry.route", -1 * MS, 1_500_000),     # cut at the open
            ("bench.window", 0, 10 * MS),
            ("bench.apply", 1 * MS, 8 * MS),
            ("registry.route", 1 * MS, 1 * MS),
            ("registry.force", 3 * MS, 3 * MS),
            ("registry.sync.results", 3 * MS, 1 * MS),
            ("registry.sync.dropped", 4 * MS, 1 * MS),
            ("registry.sync.overflow", 8_500_000, 2_500_000)]  # cut
    # busy [0, 3.5] and [5, 8]: idle [3.5, 5] and [8, 10]
    ops = [("%a = s32[8] fusion(s32[8] %x)", 0, 3_500_000),
           ("%b = s32[8] fusion(s32[8] %a)", 5 * MS, 3 * MS)]
    planes = [("/host:CPU", [("python", host)])]
    planes += [(d, [("XLA Ops", ops), ("XLA Modules", [])]) for d in devices]
    return Trace(planes)


def _spec(match, per="batches"):
    return {"match": match, "per": per}


def _reduce(mod, match, trace=None, out=OUT):
    return mod.reduce(_spec(match), trace or _synthetic(), out, {}, {})


def test_host_span_is_the_clipped_union_per_unit():
    # [0, 0.5] + [1, 2] of routing over 2 batches
    assert _reduce(host_span_per_unit, ["^registry\\.route$"]) == \
        pytest.approx(0.75)
    # nested syncs inside force count once: 0.5 + 1 + 3 + 1.5 ms
    assert _reduce(host_span_per_unit, ["^registry\\."]) == \
        pytest.approx(3.0)


def test_span_count_takes_spans_that_start_in_the_window():
    assert _reduce(span_count_per_unit, ["\\.sync\\."]) == 1.5
    # the route cut at the open started before the window
    assert _reduce(span_count_per_unit, ["^registry\\.route$"]) == 0.5


def test_idle_under_span_covers_part_of_a_gap():
    # syncs cover [3, 5] and [8.5, 10]: idle under them 1.5 + 1.5 ms
    assert _reduce(idle_under_span, ["\\.sync\\."]) == pytest.approx(1.5)
    # routing runs while the device is busy
    assert _reduce(idle_under_span, ["^registry\\.route$"]) == 0.0
    # averaged over the chips
    two = _synthetic((DEV, "/device:TPU:1"))
    assert _reduce(idle_under_span, ["\\.sync\\."], two) == \
        pytest.approx(1.5)


@pytest.mark.parametrize("mod", [host_span_per_unit, span_count_per_unit,
                                 idle_under_span])
def test_no_number_without_spans_or_units(mod):
    assert _reduce(mod, ["^queue\\."]) is None
    assert _reduce(mod, ["\\.sync\\."], out={"counts": {}}) is None


def test_idle_under_span_needs_a_device():
    assert _reduce(idle_under_span, ["\\.sync\\."], _synthetic(())) is None


@pytest.mark.parametrize("mod", [host_span_per_unit, span_count_per_unit,
                                 idle_under_span])
def test_recorded_trace_of_a_program_without_spans(mod):
    t = trace_reduce.load(os.path.join(DATA, "set_uniform_r90.xplane.pb"))
    assert _reduce(mod, ["\\.sync\\.", "^registry\\.route$"], t) is None


def _dispatched(lag_ms: float) -> Trace:
    """Two batches whose programs start ``lag_ms`` after their dispatch
    calls begin (negative: a device plane recorded early); each batch's
    read waits from 1.2 ms after the call to 4 ms."""
    host = [("bench.window", 0, 20 * MS)]
    for t in (1 * MS, 11 * MS):
        host += [("PjitFunction(_apply_v2)", t, 200_000),
                 ("PjitFunction(_apply_v2)", t + 50_000, 100_000),
                 ("registry.sync.results", t + 200_000, 2_800_000)]
    lag = int(lag_ms * MS)
    ops = [(f"%f.{i} = s32[8] fusion(s32[8] %x)", t + lag, 2 * MS)
           for i, t in enumerate((1 * MS, 11 * MS))]
    mods = [(f"jit__apply_v2({i})", t + lag, 2 * MS)
            for i, t in enumerate((1 * MS, 11 * MS))]
    return Trace([("/host:CPU", [("python", host)]),
                  (DEV, [("XLA Ops", ops), ("XLA Modules", mods)])])


def _aligned(trace):
    spec = {"match": ["\\.sync\\."], "per": "batches", "align": "_apply_v2"}
    return idle_under_span.reduce(spec, trace, OUT, {}, {})


def test_idle_under_span_moves_an_early_device_plane_to_its_dispatch():
    # programs at their dispatch: the read [1.2, 4] waits idle in [3, 4]
    assert _aligned(_dispatched(0.0)) == pytest.approx(1.0)
    # recorded 0.8 ms early, the plane is moved back: the same reading,
    # where the trace as recorded would read 1.8 ms
    assert _aligned(_dispatched(-0.8)) == pytest.approx(1.0)
    assert _reduce(idle_under_span, ["\\.sync\\."],
                   _dispatched(-0.8)) == pytest.approx(1.8)


def test_idle_under_span_leaves_a_plane_no_run_contradicts():
    # programs 0.3 ms after their dispatch: nothing to correct; the read
    # waits idle in [1.2, 1.3] and [3.3, 4]
    assert _aligned(_dispatched(0.3)) == pytest.approx(0.8)
    assert idle_under_span._shift(_dispatched(0.3), DEV, "_apply_v2") == 0.0


def test_shift_on_the_recorded_trace_is_its_earliest_program():
    # the recorded window's programs started 0.68-0.50 ms before their
    # dispatch calls: the plane moves by the largest of those
    t = trace_reduce.load(os.path.join(DATA, "set_uniform_r90.xplane.pb"))
    assert idle_under_span._dispatches(t, "_apply_v2") != []
    assert idle_under_span._shift(t, DEV, "_apply_v2") == \
        pytest.approx(0.675e-3, abs=1e-5)
