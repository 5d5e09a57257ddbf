"""Provenance block for every BENCH_*.json artifact.

``check_regression.py`` REQUIRES the block (:func:`validate_meta`): an
artifact without provenance, or one written by an emitter at a different
``SCHEMA_VERSION``, fails the guard instead of being silently compared
against floors that may mean something else.  ``SCHEMA_VERSION`` bumps
whenever a BENCH emitter changes field meaning (not on additive fields).
"""
from __future__ import annotations

import subprocess
from typing import List

SCHEMA_VERSION = 1


def validate_meta(bench: dict, path: str) -> List[str]:
    """Hard provenance gate for one BENCH payload: returns the failure
    messages (empty == valid).  A missing meta block or a schema-version
    mismatch is a FAILURE -- every current emitter writes the block via
    :func:`bench_meta`, so its absence means a stale artifact (or a
    foreign file) is about to be graded against today's floors."""
    meta = bench.get("meta")
    if meta is None:
        return [f"{path} has no meta block: stale or hand-written "
                "artifact; re-run the emitter (every benchmarks/bench_*.py "
                "writes provenance via repro.obs.meta.bench_meta)"]
    v = meta.get("schema_version")
    if v != SCHEMA_VERSION:
        return [f"{path} schema_version={v!r} != expected "
                f"{SCHEMA_VERSION}: emitter and guard disagree on field "
                "meaning; regenerate the artifact with this tree's "
                "emitters"]
    return []


def git_commit() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, check=True).stdout.strip()
    except Exception:
        return "unknown"


def bench_meta() -> dict:
    """Provenance, including the device the numbers were taken on."""
    import jax
    devices = jax.devices()
    return {"git_commit": git_commit(), "jax_version": jax.__version__,
            "schema_version": SCHEMA_VERSION,
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}
