"""Device idle time that the program's own spans whose names match the
metric's patterns cover, per unit of work the driver counted in the
traced window, in milliseconds: on each chip, the window less its busy
intervals, intersected with the union of the matching spans; averaged
over the chips.  A trace with no matching span (a program without these
spans) gives no number.

A trace puts each device plane on the host's clock only to within about
a millisecond, and by a different amount in each process, so the idle
under a span of a millisecond would follow the process, not the
program.  The metric's ``align`` names a program the driver waits on
before it dispatches the next (``_apply_v2``).  Each chip's plane is
moved later by the least amount that starts no run of that program
before the start of the host call that dispatched it
(``PjitFunction(<align>)``).  Without ``align`` the planes stay as the
trace has them."""
import bisect
import re

from bench.trace_reduce import _short_module, _union


def _dispatches(trace, prog: str) -> list:
    """Starts of the outermost host calls that dispatch ``prog`` inside
    the window (jax nests one such call inside another)."""
    name, out, end = f"PjitFunction({prog})", [], None
    for s, e, n in sorted(trace.host):
        if n == name and trace.t0 <= s < trace.t1 and (end is None
                                                        or s >= end):
            out.append(s)
            end = e
    return out


def _shift(trace, plane: str, prog: str) -> float:
    """Seconds to add to ``plane``'s times so that no run of ``prog``
    starts before its dispatch.  Each call is paired with the first run
    that starts no more than half the shortest spacing of the calls
    before it, and less than that after it."""
    calls = _dispatches(trace, prog)
    runs = sorted(s for s, _, n in trace.modules.get(plane, [])
                  if _short_module(n) == prog)
    if len(calls) < 2 or not runs:
        return 0.0
    half = min(b - a for a, b in zip(calls, calls[1:])) / 2
    shift = 0.0
    for d in calls:
        i = bisect.bisect_left(runs, d - half)
        if i < len(runs) and runs[i] < d + half:
            shift = max(shift, d - runs[i])
    return shift


def _idle(trace, plane: str, shift: float) -> list:
    ops = [(s + shift, e + shift, n) for s, e, n in trace.ops[plane]]
    busy = _union([(s, e) for s, e, _ in trace._clip(ops)])
    edges = [trace.t0] + [x for iv in busy for x in iv] + [trace.t1]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def _overlap(a: list, b: list) -> float:
    """Seconds in both of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(spec, trace, out, config, device):
    units = out["counts"].get(spec["per"], 0)
    rx = re.compile("|".join(spec["match"]))
    spans = _union([(s, e) for s, e, n in trace._clip(trace.host)
                    if rx.search(n)])
    if not units or not spans or not trace.devices:
        return None
    prog = spec.get("align")
    idle = sum(_overlap(_idle(trace, p, _shift(trace, p, prog)
                              if prog else 0.0), spans)
               for p in trace.devices)
    return idle / len(trace.devices) / units * 1e3
