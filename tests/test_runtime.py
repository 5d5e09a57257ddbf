"""Fault-tolerance runtime: resilient loop crash/restart, straggler
monitor, data-pipeline determinism, gradient compression, GPipe."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import SyntheticTokens, Prefetcher
from repro.runtime.ft import StragglerMonitor, ResilientLoop
from repro.store.checkpoint import CheckpointManager
from repro.optim.compress import compressed_psum, quantize, dequantize


def test_data_determinism_and_seek():
    a = SyntheticTokens(100, 8, 4, seed=1)
    b1 = next(iter(a))
    a2 = SyntheticTokens(100, 8, 4, seed=1)
    a2.seek(0)
    b2 = next(iter(a2))
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # shards are disjoint streams
    s0 = SyntheticTokens(100, 8, 4, shard=0, num_shards=2, seed=1)
    s1 = SyntheticTokens(100, 8, 4, shard=1, num_shards=2, seed=1)
    assert not np.array_equal(next(iter(s0))["tokens"],
                              next(iter(s1))["tokens"])


def test_prefetcher():
    it = iter(SyntheticTokens(100, 8, 2, seed=0))
    limited = (next(it) for _ in range(5))
    out = list(Prefetcher(limited, depth=2))
    assert len(out) == 5


def test_straggler_monitor():
    m = StragglerMonitor(4, ratio=1.5)
    for _ in range(10):
        m.record(np.array([1.0, 1.0, 1.0, 3.0]))
    assert m.stragglers() == [3]
    w = m.rebalanced_weights()
    assert w[3] < w[0] and abs(w.sum() - 1) < 1e-9


def test_resilient_loop_crash_restart(tmp_path):
    """Inject a failure mid-training; the loop must restore the last
    SOFT-committed step and converge to the same final state as a run
    without failures (deterministic replay)."""
    def run(fail_at, d):
        mgr = CheckpointManager(str(d), keep=3)
        data = SyntheticTokens(50, 4, 2, seed=3)

        def step_fn(state, batch):
            s = state["x"] + float(batch["tokens"].sum() % 97)
            return {"x": s, "step": state["step"] + 1}, {}

        def restore_fn(m, like):
            st = m.latest_step()
            if st is None:
                return None
            arrs = m.restore(st)
            return ({"x": float(arrs["x"]), "step": int(arrs["step"])}, st)

        def snapshot_fn(state):
            return {"x": np.array(state["x"]), "step": np.array(state["step"])}

        loop = ResilientLoop(mgr, data, save_every=4, async_save=False)
        state, steps = loop.run({"x": 0.0, "step": 0}, step_fn, 20,
                                restore_fn, snapshot_fn, fail_at=fail_at)
        mgr.close()
        return state["x"]

    clean = run(None, tmp_path / "clean")
    crashed = run(11, tmp_path / "crashed")
    assert clean == crashed


def test_quantize_roundtrip():
    x = np.random.default_rng(0).standard_normal(1000).astype(np.float32)
    q, s = quantize(jnp.asarray(x))
    err = np.abs(np.array(dequantize(q, s)) - x).max()
    assert err <= float(s) * 0.51 + 1e-6


def test_compressed_psum_error_feedback():
    """int8 all-reduce with error feedback: mean error shrinks vs one-shot."""
    mesh = jax.make_mesh((1,), ("d",), axis_types=(jax.sharding.AxisType.Auto,))

    def body(g, r):
        return compressed_psum(g, r, "d")

    f = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(),) * 2,
        out_specs=(jax.sharding.PartitionSpec(),) * 2, check_vma=False))
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal(512), jnp.float32)
    r = jnp.zeros(512)
    total_true = np.zeros(512)
    total_approx = np.zeros(512)
    for _ in range(8):
        out, r = f(g, r)
        total_true += np.array(g)
        total_approx += np.array(out)
    # error feedback keeps the ACCUMULATED estimate tight
    rel = np.abs(total_approx - total_true).max() / np.abs(total_true).max()
    assert rel < 0.02


def test_gpipe_matches_sequential():
    from repro.launch.pipeline import gpipe_fn
    n = min(4, len(jax.devices()))
    if n < 2:
        pytest.skip("needs >=2 local devices for a pipeline")
    mesh = jax.make_mesh((n,), ("pipe",), axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.standard_normal((n, 8, 8)) * 0.3, jnp.float32)
    xs = jnp.asarray(rng.standard_normal((6, 2, 8)), jnp.float32)

    def stage(w, x):
        return jnp.tanh(x @ w)

    run = gpipe_fn(stage, mesh)
    got = run(ws, xs)
    ref = xs
    for i in range(n):
        ref = jnp.tanh(ref @ ws[i])
    np.testing.assert_allclose(np.array(got), np.array(ref), atol=1e-5)
