"""A kernel's share of its roofline: the least time the chip could take for
the work the operation needs (bytes over peak bandwidth, or operations
over peak rate, whichever is larger), over the device time of the
kernel's operations (or, with ``"line": "modules"``, of a whole program).

The needed work comes from ``bench/work.py`` by the metric's ``work``
name, per unit the driver counted; the peaks from ``bench/peaks.json`` by
the device kind.  A kernel absent from the trace gives no number."""
from bench import work


def reduce(spec, trace, out, config, device):
    units = out["counts"].get(spec["per"], 0)
    seconds = trace.device_s(spec["match"], spec.get("line", "ops"))
    if not units or not seconds:
        return None
    need = getattr(work, spec["work"])(config)
    peak = work.peaks(device["kind"])
    least = max(need.get("bytes", 0) * units / peak["hbm_bytes_per_s"],
                need.get("int_ops", 0) * units / peak["int8_ops_per_s"],
                need.get("flops", 0) * units / peak["bf16_flops_per_s"])
    return 100.0 * least / seconds
