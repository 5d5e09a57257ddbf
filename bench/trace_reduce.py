"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time and idle share, device time per program or
kernel name, and the breakdown of where the time went.

The traced window is the host span ``bench.window`` that the harness
opens around a driver's loop.  Device time is read from each TPU plane's
``XLA Ops`` line (one event per executed operation, kernels by their
own names) and ``XLA Modules`` line (one event per executed program,
named after the jitted function).  Busy time is the union of the op
intervals inside the window, averaged over the chips that ran any.

    python3 bench/trace_reduce.py <trace dir or .xplane.pb>   # a summary
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def _xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _union(iv: list) -> list:
    """Merge (start, end) intervals."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    """The parts of one trace the reducers read.  Times are seconds on the
    profiler's clock."""

    def __init__(self, planes: list):
        """``planes``: [(plane name, [(line name, [(name, start_ns,
        duration_ns)])])] -- the shape ``load`` reads from the file."""
        self.ops, self.modules, self.host = {}, {}, []
        window = None
        for pname, lines in planes:
            if pname.startswith(DEVICE_PREFIX):
                for lname, events in lines:
                    if lname == OPS_LINE:
                        self.ops[pname] = _events(events)
                    elif lname == MODULES_LINE:
                        self.modules[pname] = _events(events)
            elif pname.startswith("/host:"):
                for lname, events in lines:
                    ev = _events(events)
                    win = [e for e in ev if e[2] == WINDOW_SPAN]
                    if win:
                        window = win[0][:2]
                        self.host = ev
        if window is None:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        self.t0, self.t1 = window
        self.window_s = self.t1 - self.t0
        self.devices = sorted(p for p in self.ops if self.ops[p])

    def _clip(self, events: list) -> list:
        return [(max(s, self.t0), min(e, self.t1), n) for s, e, n in events
                if e > self.t0 and s < self.t1]

    def busy_intervals(self, plane: str) -> list:
        return _union([(s, e) for s, e, _ in self._clip(self.ops[plane])])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(p))
                   for p in self.devices) / len(self.devices)

    def device_s(self, patterns: list, line: str = "ops") -> float:
        """Seconds of the events whose name matches any of ``patterns``
        (regular expressions), summed over the window and averaged over
        the chips; ``line`` is "ops" or "modules"."""
        src = self.ops if line == "ops" else self.modules
        rx = re.compile("|".join(patterns))
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e, n in self._clip(src.get(p, []))
                       if rx.search(n))
                   for p in self.devices) / len(self.devices)

    def top_ops(self, k: int = 10) -> list:
        """The operations that took the most device time, each named
        ``<program>:<instruction>:<opcode>``, averaged over the chips."""
        total = defaultdict(float)
        for p in self.devices:
            mods = sorted(self._clip(self.modules.get(p, [])))
            j = 0
            for s, e, n in sorted(self._clip(self.ops[p])):
                while j < len(mods) and mods[j][1] < s:
                    j += 1
                mod = mods[j][2] if j < len(mods) and mods[j][0] <= s \
                    else "?"
                total[f"{_short_module(mod)}:{_short_op(n)}"] += \
                    (e - s) / len(self.devices)
        return sorted(total.items(), key=lambda x: -x[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle device time inside the window, by what the host was doing:
        the innermost benchmark span and the innermost host event that
        cover each gap's midpoint."""
        gaps = []
        for p in self.devices:
            busy = self.busy_intervals(p)
            edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
            gaps += [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                     if e > s]
        gaps.sort(key=lambda g: g[0] + g[1])
        labels = _doing(sorted(self.host), [(s + e) / 2 for s, e in gaps])
        total = defaultdict(float)
        for (s, e), label in zip(gaps, labels):
            total[label] += (e - s) / len(self.devices)
        return sorted(total.items(), key=lambda x: -x[1])[:k]

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops()],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps()]}


def _short_module(name: str) -> str:
    """``jit__apply_v2(1234)`` -> ``_apply_v2``."""
    return re.sub(r"^jit_|\(\d+\)$", "", name)


def _short_op(name: str) -> str:
    """An HLO instruction's text -> ``<name>:<opcode>``."""
    head, _, rest = name.partition(" = ")
    op = re.search(r"\s([a-z][\w\-]*)\(", " " + rest)
    return f"{head}:{op.group(1)}" if op else head


def _events(events) -> list:
    return [(s * 1e-9, (s + d) * 1e-9, n) for n, s, d in events]


def _doing(host: list, points: list) -> list:
    """For each time in ``points`` (ascending), a label for what the host
    thread was doing: its innermost benchmark span and innermost event.
    The events of one thread nest, so a stack sweep finds them."""
    stack, i, out = [], 0, []
    for t in points:
        while i < len(host) and host[i][0] <= t:
            while stack and stack[-1][1] < host[i][0]:
                stack.pop()
            if host[i][2] != WINDOW_SPAN:
                stack.append(host[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        span = next((n for _, _, n in reversed(stack)
                     if n.startswith(SPAN_PREFIX)), None)
        inner = stack[-1][2] if stack else None
        if inner is None:
            out.append("host: outside any event")
        elif inner == span:
            out.append(span)
        else:
            out.append(f"{span or 'host'} > {inner}")
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(_xplane(path))
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]))
        planes.append((plane.name, lines))
    return Trace(planes)


def dump(path: str) -> dict:
    """Every plane and line with its event count and the names that took
    the most time: what to read before keying a metric on a name."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(_xplane(path))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            total = defaultdict(lambda: [0, 0.0])
            for e in line.events:
                total[e.name][0] += 1
                total[e.name][1] += e.duration_ns * 1e-9
            top = sorted(total.items(), key=lambda x: -x[1][1])[:25]
            out.append({"plane": plane.name, "line": line.name,
                        "events": sum(v[0] for v in total.values()),
                        "top": [[n, c, s] for n, (c, s) in top]})
    return {"lines": out}


if __name__ == "__main__":
    print(json.dumps(dump(sys.argv[1]), indent=1))
