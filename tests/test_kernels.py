"""Per-kernel shape/dtype sweeps: pallas_call (interpret) vs pure-jnp ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.nvm import VALID
from repro.kernels.hash_probe.ops import build_buckets, lookup
from repro.kernels.hash_probe.kernel import probe_pallas
from repro.kernels.hash_probe.ref import probe_ref
from repro.kernels.recovery_scan.kernel import scan_pallas
from repro.kernels.recovery_scan.ref import scan_ref


@pytest.mark.parametrize("nb,w,b", [(64, 8, 8), (256, 8, 128),
                                    (512, 16, 256), (1024, 8, 64)])
def test_hash_probe_sweep(nb, w, b):
    rng = np.random.default_rng(nb + b)
    n = nb * w // 2
    keys = jnp.asarray(rng.choice(10 ** 6, n, replace=False), jnp.int32)
    cur = jnp.asarray(rng.integers(0, 5, n), jnp.int32)
    bk, bi, ovf = build_buckets(keys, cur, nb=nb, w=w)
    q = jnp.concatenate([keys[: b // 2],
                         jnp.asarray(rng.integers(2 * 10 ** 6, 3 * 10 ** 6,
                                                  b - b // 2), jnp.int32)])
    got = lookup(bk, bi, q, use_pallas=True)
    ref = lookup(bk, bi, q, use_pallas=False)
    np.testing.assert_array_equal(np.array(got), np.array(ref))


def test_hash_probe_semantics():
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.choice(10 ** 6, 256, replace=False), jnp.int32)
    cur = jnp.full((256,), VALID, jnp.int32)
    bk, bi, ovf = build_buckets(keys, cur, nb=128, w=8)
    assert int(ovf) == 0
    got = np.array(lookup(bk, bi, keys[:128], use_pallas=True))
    np.testing.assert_array_equal(got, np.arange(128))


@pytest.mark.parametrize("n,nt", [(1024, 128), (8192, 1024), (65536, 8192)])
def test_recovery_scan_sweep(n, nt):
    rng = np.random.default_rng(n)
    stages = jnp.asarray(rng.integers(0, 5, n), jnp.int32)
    m1, h1 = scan_pallas(stages, nt=nt)
    m2, h2 = scan_ref(stages)
    np.testing.assert_array_equal(np.array(m1), np.array(m2))
    np.testing.assert_array_equal(np.array(h1), np.array(h2))


def test_interpret_follows_the_platform(monkeypatch):
    """Interpreted off the TPU, compiled on it; an interpret request on
    the TPU raises instead of running the interpreter there."""
    from repro.kernels import resolve_interpret
    assert resolve_interpret() is (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    with pytest.raises(ValueError, match="interpret"):
        resolve_interpret(True)
