"""Each driver drives a whole run on the CPU at a tiny size, without the
harness's look for a chip: ``correct`` comes out true on the program as it
is, and false with the timed path broken underneath, once for each fault
a cell can have.  The control (buffered durability in the registry's
place) comes out not correct in every cell.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, sut
from bench.run import execute

SMALL = dict(capacity=2048, shards=4, batch=128, queue_capacity=512,
             key_range=2048)
CELLS = ["spine_zipf_r80", "set_uniform_r90", "set_crash_recover",
         "spine_zipf_sat"]
PREFILL_BATCHES = 8          # key_range / 2 / batch


# the open loop's cell waits outside BENCHMARK.json (PERF.md, Open
# questions); its files are driven here all the same
FILES = {"spine_zipf_r80": ("spine_probe_2e20", "zipf_r80", ["p90_ms"])}


def small(name: str) -> dict:
    if name in FILES:
        cfg, traffic, e2e = FILES[name]
        wl = {"cell": {"name": name},
              "config": harness.load_json(harness.BENCH, "configs",
                                          cfg + ".json"),
              "traffic": harness.load_json(harness.BENCH, "traffic",
                                           traffic + ".json"),
              "end_to_end": [{"name": n, "unit": "ms"} for n in e2e]
              + [{"name": "setup_s", "unit": "s"}], "per_layer": []}
    else:
        wl = harness.workload(name)
    wl["config"].update(SMALL)
    wl["traffic"]["ring_batches"] = 32
    if wl["traffic"]["driver"] == "open_loop":
        wl["traffic"]["rate_per_s"] = 4000.0
    return wl


def run(name: str, control: bool = False, seed: int = 2**31 + 3) -> dict:
    return execute(small(name), seed, 0.6, False, time.perf_counter(),
                   control=control)


def fault_answer(m):
    """One answer altered where it is produced."""
    inner, calls = m.apply, [0]

    def apply(ops, keys, values=None):
        res = np.array(inner(ops, keys, values), bool)
        calls[0] += 1
        if calls[0] == PREFILL_BATCHES + 3:
            res[0] = ~res[0]
        return res
    m.apply = apply


def fault_frozen(m):
    """A step that returns its state unchanged."""
    inner, calls = m.apply, [0]

    def apply(ops, keys, values=None):
        calls[0] += 1
        if calls[0] <= PREFILL_BATCHES:
            return inner(ops, keys, values)
        saved = jax.tree.map(jnp.copy, m.state)
        res = inner(ops, keys, values)
        m.state = saved
        return res
    m.apply = apply


def fault_half(m):
    """Half of the batch left out; the rest applied."""
    inner, calls = m.apply, [0]

    def apply(ops, keys, values=None):
        calls[0] += 1
        ops = np.array(ops, np.int32)
        if calls[0] > PREFILL_BATCHES:
            ops[ops.size // 2:] = 3                   # OP_NOP
        return inner(ops, keys, values)
    m.apply = apply


def fault_psync(m):
    """One psync count altered."""
    cls = type(m)
    m.__class__ = type("Faulty" + cls.__name__, (cls,), {
        "psyncs": property(lambda self: cls.psyncs.fget(self) + 1)})


FAULTS = {"answer": fault_answer, "frozen": fault_frozen,
          "half": fault_half, "psync": fault_psync}


@pytest.fixture
def faulty(monkeypatch):
    def arm(fault):
        build_registry, build_spine = sut.build_registry, sut.build_spine

        def registry(config, metrics=None):
            m = build_registry(config, metrics)
            fault(m)
            return m

        def spine(config, metrics):
            m, rq, sq = build_spine(config, metrics)
            fault(m)
            return m, rq, sq
        monkeypatch.setattr(sut, "build_registry", registry)
        monkeypatch.setattr(sut, "build_spine", spine)
    return arm


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = run(cell)
    assert line["correct"], line["checks"]
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    e2e = {m["name"] for m in small(cell)["end_to_end"]}
    assert set(line["metrics"]) == e2e
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["info"]["compiles_in_window"] is None or \
        line["info"]["compiles_in_window"] == 0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(cell, fault, faulty):
    faulty(FAULTS[fault])
    line = run(cell)
    assert not line["correct"], (fault, line["checks"])


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    line = run(cell, control=True)
    assert not line["correct"]
    assert line["checks"]["psync_gap"]["value"] > 0
