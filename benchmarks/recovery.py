"""Section 2.1/6: recovery-scan cost vs set size (crash -> rebuilt set),
plus the Pallas recovery_scan kernel vs the jnp reference."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as E
from repro.core.engine import SetSpec
from repro.kernels.recovery_scan.ops import recovery_scan
from benchmarks.common import Result, fmt_row

_FILL_BATCH = 4096    # keeps _dedup_first's (B, B) lane matrix small


def run(quick: bool = False):
    rows = []
    sizes = (1 << 12, 1 << 14) if quick else (1 << 12, 1 << 15, 1 << 18)
    for n in sizes:
        spec = SetSpec(capacity=n, mode="soft")
        state = E.make_state(spec)
        for lo in range(0, n // 2, _FILL_BATCH):
            keys = jnp.arange(lo, min(lo + _FILL_BATCH, n // 2),
                              dtype=jnp.int32)
            state, _ = E.insert(state, keys, keys, spec=spec)
        u = jnp.zeros((n,), jnp.float32)

        rec = jax.jit(lambda state, u, spec=spec:
                      E.crash_and_recover(state, u, spec=spec))

        s2, hist = rec(state, u)
        jax.block_until_ready(s2.table)
        t0 = time.perf_counter()
        s2, hist = rec(state, u)
        jax.block_until_ready(s2.table)
        dt = time.perf_counter() - t0
        assert int(s2.size) == n // 2
        res = Result(ops_per_sec=n / dt, psync_per_op=0.0,
                     psync_per_update=0.0, rounds=1)
        rows.append(fmt_row(f"recovery_n{n}", res,
                            {"nodes_per_sec": f"{n / dt:.0f}",
                             "live": int(hist[3])}))
        # kernel-only validity scan: jnp reference vs Pallas
        persisted = s2.cur
        for tag, use_pallas in (("ref", False), ("pallas", True)):
            if (use_pallas and n > (1 << 12)
                    and jax.default_backend() != "tpu"):
                continue          # interpreted off the TPU: keep it small
            t0 = time.perf_counter()
            mask, hist2 = recovery_scan(persisted, use_pallas=use_pallas)
            jax.block_until_ready(hist2)
            dt2 = time.perf_counter() - t0
            rows.append(fmt_row(
                f"recovery_scan_{tag}_n{n}",
                Result(n / dt2, 0, 0, 1), {"live": int(hist2[3])}))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
