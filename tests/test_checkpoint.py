"""Durable checkpoint store: commit semantics, kill-9 torn writes (via
hypothesis-driven truncation), GC-by-destroy, async save, elastic restore,
fsync accounting (SOFT vs link-free)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="dev-only dependency; pip install -r requirements-dev.txt")
from hypothesis import given, settings, strategies as st

from repro.store.checkpoint import CheckpointManager
from repro.store.tensorstore import DurableArea


def tree(step):
    return {"layer": {"w": np.full((4, 4), float(step)),
                      "b": np.arange(step + 1, dtype=np.int32)},
            "step_arr": np.array([step])}


def test_save_restore_roundtrip(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=3)
    for s in (1, 2, 3):
        m.save(s, tree(s))
    m.close()
    m2 = CheckpointManager(str(tmp_path))
    assert m2.latest_step() == 3
    r = m2.restore(like=tree(3))
    np.testing.assert_array_equal(r["layer"]["w"], tree(3)["layer"]["w"])
    r1 = m2.restore(step=2, like=tree(2))
    np.testing.assert_array_equal(r1["layer"]["w"], tree(2)["layer"]["w"])
    m2.close()


def test_gc_patches_deleted(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=1)
    m.save(1, tree(1))
    m.save(2, tree(2))
    m.close()
    m2 = CheckpointManager(str(tmp_path))
    assert m2.committed == [2]          # step 1 destroyed, never rewritten
    m2.close()


def test_single_fsync_per_record_soft(tmp_path):
    m = CheckpointManager(str(tmp_path), mode="soft", keep=5)
    m.save(1, tree(1))
    # 3 leaves + 1 commit record == 4 fsyncs, the SOFT bound
    assert m.fsyncs == 4
    m.close()
    m2 = CheckpointManager(str(tmp_path) + "_lf", mode="linkfree", keep=5)
    m2.save(1, tree(1))
    assert m2.fsyncs == 8               # link-free pays the pointer persist
    m2.close()


def test_async_save(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2)
    fut = m.save(1, tree(1), async_=True)
    fut.result()
    m.save(2, tree(2), async_=True)
    m.wait()
    assert m.committed[-1] == 2
    m.close()


@settings(max_examples=25, deadline=None)
@given(cut=st.integers(1, 400))
def test_kill9_truncation_never_corrupts(tmp_path_factory, cut):
    """Truncating the tail anywhere must leave all fully-committed earlier
    steps restorable (the paper's invalid-node rule on disk)."""
    d = tmp_path_factory.mktemp("ckpt")
    m = CheckpointManager(str(d), keep=5)
    m.save(1, tree(1))
    size1 = os.path.getsize(m.area.path)
    m.save(2, tree(2))
    m.close()
    path = os.path.join(str(d), "area_00000.pdn")
    size2 = os.path.getsize(path)
    keep_bytes = max(size1, size2 - cut)
    with open(path, "r+b") as f:
        f.truncate(keep_bytes)
    m2 = CheckpointManager(str(d))
    assert 1 in m2.committed
    r = m2.restore(step=1, like=tree(1))
    np.testing.assert_array_equal(r["layer"]["w"], tree(1)["layer"]["w"])
    m2.close()


def test_flipped_byte_detected(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=5)
    m.save(1, tree(1))
    m.close()
    path = os.path.join(str(tmp_path), "area_00000.pdn")
    with open(path, "r+b") as f:       # corrupt a payload byte
        f.seek(64)
        b = f.read(1)
        f.seek(64)
        f.write(bytes([b[0] ^ 0xFF]))
    recs = DurableArea.scan(path)
    m2 = CheckpointManager(str(tmp_path))
    assert 1 not in m2.committed        # CRC catches the flip
    m2.close()


def test_elastic_restore_new_sharding(tmp_path):
    """Restore the same logical checkpoint onto a different device layout."""
    m = CheckpointManager(str(tmp_path), keep=2)
    t = {"w": np.arange(16, dtype=np.float32).reshape(4, 4)}
    m.save(1, t)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    sh = {"w": jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", None))}
    like = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)}
    r = m.restore(like=like, shardings=sh)
    np.testing.assert_array_equal(np.array(r["w"]), t["w"])
    assert r["w"].sharding.spec == jax.sharding.PartitionSpec("data", None)
    m.close()
