"""Recovery classification: the Pallas scan kernel or its jnp reference."""
from __future__ import annotations

from repro.kernels.recovery_scan.kernel import scan_pallas
from repro.kernels.recovery_scan.ref import scan_ref


def recovery_scan(persisted, *, use_pallas=True):
    """persisted i32[N] -> (member mask bool[N], stage histogram i32[5])."""
    if use_pallas:
        return scan_pallas(persisted)
    return scan_ref(persisted)
