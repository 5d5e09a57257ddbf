"""Device time of the events that match the metric's names, per unit of
work the driver counted in the traced window (a round, a batch, a crash),
in milliseconds."""


def reduce(spec, trace, out, config, device):
    units = out["counts"].get(spec["per"], 0)
    seconds = trace.device_s(spec["match"], spec["line"])
    if not units or not seconds:
        return None
    return seconds / units * 1e3
