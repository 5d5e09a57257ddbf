"""Peaks by device kind, needed work, and the roofline reducer."""
import pytest

from bench import work
from bench.reducers import roofline
from bench.trace_reduce import Trace


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("TPU v99 imaginary")


def test_v5e_peaks():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12


def test_needed_bytes():
    assert work.lookup_bytes({"backend": "bucket"})["bytes"] == 8 + 64
    assert work.lookup_bytes({"backend": "probe"})["bytes"] == 8 + 128
    assert work.recover_bytes({})["bytes"] == 12


def _trace(kernel_ns):
    dev = "/device:TPU:0"
    return Trace([
        ("/host:CPU", [("python", [("bench.window", 0, 10_000_000)])]),
        (dev, [("XLA Ops", [("%vmap_jit_scan_pallas___ = s32[8] "
                             "custom-call(s32[8])", 1_000_000, kernel_ns)]),
               ("XLA Modules", [("jit_recover", 1_000_000, kernel_ns)])]),
    ])


def test_roofline_share():
    spec = {"match": ["^%[^ ]*scan_pallas[^ ]* = "], "work": "recover_bytes",
            "per": "slots"}
    out = {"counts": {"slots": 273_000}}         # 3.276 MB: 4 us at peak
    got = roofline.reduce(spec, _trace(8_000), out, {},
                          {"kind": "TPU v5 lite"})
    assert got == pytest.approx(50.0)
    assert roofline.reduce(spec, _trace(8_000), {"counts": {}}, {},
                           {"kind": "TPU v5 lite"}) is None
