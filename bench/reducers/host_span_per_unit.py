"""Host time inside the program's own spans (``repro.obs.span``) whose
names match the metric's patterns, per unit of work the driver counted
in the traced window, in milliseconds: the union of the matching spans,
clipped to the window, so a nested match counts once.  A trace with no
matching span (a program without these spans) gives no number."""
import re

from bench.trace_reduce import _union


def reduce(spec, trace, out, config, device):
    units = out["counts"].get(spec["per"], 0)
    rx = re.compile("|".join(spec["match"]))
    spans = [(s, e) for s, e, n in trace._clip(trace.host) if rx.search(n)]
    if not units or not spans:
        return None
    return sum(e - s for s, e in _union(spans)) / units * 1e3
