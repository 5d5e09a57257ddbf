"""Host-clock seconds the benchmark spent inside its span around one
program call, per call in the traced window, in milliseconds."""


def reduce(spec, trace, out, config, device):
    units = out["counts"].get(spec["per"], 0)
    if not units:
        return None
    return out["counts"][spec["span_seconds"]] / units * 1e3
