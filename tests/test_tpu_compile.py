"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jaxlib; it compiles for a chip that is
described, not attached.  These cases lower each kernel at the real
serving geometry with ``interpret=False`` and require the Mosaic kernel
(``tpu_custom_call``) in the compiled HLO -- refusals that interpret mode
cannot show (unaligned blocks, VMEM overruns) fail here, on the CPU.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this module.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.hash_probe.kernel import probe_pallas
from repro.kernels.hash_probe.ops import table_lookup
from repro.kernels.recovery_scan.kernel import scan_pallas

BATCH = 1024              # serving batch (ServeConfig.batch)
SHARDS = 8                # serving registry shards
SHARD_POOL = 1 << 17      # 2^20 slots / 8 shards


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel missing from the compiled HLO"


@pytest.mark.parametrize("nb", [1 << 15, 1 << 18],
                         ids=["per_shard_2e17_slots", "flat_2e20_slots"])
def test_probe_pallas_compiles(one_chip, nb):
    table = _i32((nb, 8), one_chip)
    q = _i32((BATCH,), one_chip)
    _assert_kernel(functools.partial(probe_pallas, interpret=False),
                   table, table, q, q)


def test_table_lookup_compiles(one_chip):
    fn = functools.partial(table_lookup, max_probe=128, interpret=False)
    _assert_kernel(fn, _i32((4 * SHARD_POOL,), one_chip),
                   _i32((SHARD_POOL,), one_chip), _i32((BATCH,), one_chip))


def test_scan_pallas_compiles_flat(one_chip):
    _assert_kernel(functools.partial(scan_pallas, interpret=False),
                   _i32((1 << 20,), one_chip))


def test_recovery_scan_compiles_vmapped_over_shards(one_chip):
    _assert_kernel(jax.vmap(functools.partial(scan_pallas, interpret=False)),
                   _i32((SHARDS, SHARD_POOL), one_chip))
