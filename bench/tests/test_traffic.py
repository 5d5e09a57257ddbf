"""The traffic generator: seeded, bounded, and YCSB's zipfian law."""
import numpy as np
import pytest

from bench.traffic_gen import (KEY_ID_SPACE, KeyUniverse, OpStream,
                               PoissonArrivals, Zipfian)

TRAFFIC = {"mix": {"contains": 50, "insert": 25, "remove": 25},
           "keys": {"dist": "zipfian", "theta": 0.99}}


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 3, -7])
def test_same_seed_same_stream(seed):
    a, b = (OpStream(TRAFFIC, KeyUniverse(seed, 4096, 0.5), seed)
            for _ in range(2))
    for x, y in zip(a.draw(1000), b.draw(1000)):
        np.testing.assert_array_equal(x, y)


def test_universe_spreads_keys_and_prefills_half():
    u, v = KeyUniverse(3, 1 << 14, 0.5), KeyUniverse(4, 1 << 14, 0.5)
    np.testing.assert_array_equal(u.ids, v.ids)     # the seed's own work
    assert not np.array_equal(u.prefill, v.prefill)
    assert np.unique(u.ids).size == u.ids.size
    assert u.ids.min() >= 0 and u.ids.max() < KEY_ID_SPACE
    assert u.ids.max() > KEY_ID_SPACE // 2          # not narrowed
    assert u.prefill.size == 1 << 13
    assert np.isin(u.prefill, u.ids).all()


def test_zipfian_follows_the_law():
    z = Zipfian(1 << 16, 0.99)
    r = z.ranks(np.random.default_rng(0).random(1 << 20))
    assert r.min() == 0 and r.max() < 1 << 16
    share0 = (r == 0).mean()
    assert share0 == pytest.approx(1 / z.zetan, rel=0.05)
    share1 = (r == 1).mean()
    assert share1 == pytest.approx(0.5 ** 0.99 / z.zetan, rel=0.05)


def test_mix_shares():
    s = OpStream({"mix": {"contains": 90, "insert": 5, "remove": 5},
                  "keys": {"dist": "uniform"}}, KeyUniverse(1, 4096, 0.5), 1)
    ops, _, _ = s.draw(200000)
    assert np.bincount(ops, minlength=3)[:3] / ops.size == pytest.approx(
        [0.9, 0.05, 0.05], abs=0.01)


def test_poisson_arrivals_hold_the_rate():
    s = OpStream(TRAFFIC, KeyUniverse(1, 4096, 0.5), 1)
    arr = PoissonArrivals(50000.0, s, 1)
    t, _, _ = arr.take(2.0, 1 << 30)
    assert t.size == pytest.approx(100000, rel=0.02)
    assert np.all(np.diff(t) >= 0) and t[-1] <= 2.0
