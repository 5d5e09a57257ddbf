"""Share of the traced window in which no operation ran on the device."""


def reduce(spec, trace, out, config, device):
    if not trace.devices:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
