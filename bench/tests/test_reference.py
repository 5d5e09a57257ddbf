"""The vectorised reference against the plain dict loop, and the control's
broken guarantee."""
import numpy as np
import pytest

from bench.reference import BufferedRegistry, RefRegistry, ref_apply
from bench.traffic_gen import OP_NOP


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_vectorised_reference_equals_dict_loop(seed):
    rng = np.random.default_rng(seed)
    ids = rng.choice(1 << 30, 64, replace=False).astype(np.int32)
    ref, plain = RefRegistry(ids), {}
    for _ in range(50):
        ops = rng.integers(0, 4, 96).astype(np.int32)    # NOP lanes too
        keys = ids[rng.integers(0, ids.size, 96)]        # many repeats
        keys = np.where(ops == OP_NOP, 0, keys).astype(np.int32)
        vals = rng.integers(0, 1 << 31, 96).astype(np.int32)
        want = ref_apply(plain, np.where(ops == OP_NOP, -1, ops), keys,
                         vals)
        np.testing.assert_array_equal(ref.apply(ops, keys, vals), want)
    present, vals = ref.lookup(ids)
    assert present.sum() == len(plain) == ref.size()
    assert vals[present].tolist() == [plain[int(k)] for k in ids[present]]


def test_reference_counts_one_psync_per_successful_update():
    ids = np.arange(10, 20, dtype=np.int32)
    ref = RefRegistry(ids)
    ops = np.array([1, 1, 1, 2, 0], np.int32)        # insert x3, remove
    keys = np.array([10, 10, 11, 11, 12], np.int32)
    got = ref.apply(ops, keys, keys)
    assert got.tolist() == [True, False, True, True, False]
    assert ref.psyncs == 3 and ref.size() == 1
    ref.crash()
    assert ref.psyncs == 0 and ref.size() == 1


def test_control_loses_the_open_epoch_and_buffers_psyncs():
    ids = np.arange(100, dtype=np.int32)
    ctl = BufferedRegistry(ids, epoch=4)
    ins = np.full(10, 1, np.int32)
    for i in range(6):
        ctl.apply(ins, ids[10 * i:10 * i + 10], ids[10 * i:10 * i + 10])
    assert ctl.psyncs == 1 and ctl.size() == 60
    ctl.crash()
    assert ctl.size() == 40                  # batches 5 and 6 were open
