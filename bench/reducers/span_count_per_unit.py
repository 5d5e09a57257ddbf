"""How many of the program's own spans whose names match the metric's
patterns start inside the traced window, per unit of work the driver
counted there.  Over ``.sync.`` spans, each of which wraps exactly one
device-to-host read, this is the host syncs per unit.  A trace with no
matching span (a program without these spans) gives no number."""
import re


def reduce(spec, trace, out, config, device):
    units = out["counts"].get(spec["per"], 0)
    rx = re.compile("|".join(spec["match"]))
    n = sum(1 for s, _, name in trace.host
            if trace.t0 <= s < trace.t1 and rx.search(name))
    if not units or not n:
        return None
    return n / units
