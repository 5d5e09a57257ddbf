"""What every cell's run shares: the files a workload is made of, the
compile clock, the measured window (traced or not) and the result line.

A workload's name in ``BENCHMARK.json`` leads to everything else by name:

  configs[].file                 the configuration (sizes, guarantees)
  bench/traffic/<traffic>.json   the traffic mix; names its driver
  bench/drivers/<driver>.py      the loop that drives the program
  bench/metrics/<metric>.json    one per-layer metric; names its reducer
  bench/reducers/<reducer>.py    reads that metric from the trace, the
                                 benchmark's host spans or its counters
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import json
import os
import shutil
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")     # fixed: part of the key


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def workload(name: str) -> dict:
    """The cell ``name`` with its configuration, traffic and metric files
    resolved: {"cell", "config", "traffic", "end_to_end", "per_layer"}."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def reports(m) -> bool:
        return name in m.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if reports(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if reports(m) and m["moves"] in moved]
    return {"cell": cell,
            "config": load_json(ROOT, cfg["file"]),
            "traffic": load_json(BENCH, "traffic", cell["traffic"] + ".json"),
            "end_to_end": e2e,
            "per_layer": per_layer}


def driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def reducer(name: str):
    return importlib.import_module(f"bench.reducers.{name}")


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, with
    every program cached however fast it compiled."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Sums JAX's backend-compile seconds (cache reads included) and counts
    compiles and persistent-cache hits, process-wide."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class GCWatch:
    """Counts the interpreter's garbage collections and their pauses."""

    def __init__(self):
        self.n = [0, 0, 0]
        self.max_ms = self.total_ms = 0.0
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            ms = (time.perf_counter() - self._t) * 1e3
            self.n[info["generation"]] += 1
            self.max_ms = max(self.max_ms, ms)
            self.total_ms += ms

    def summary(self) -> dict:
        return {"collections": self.n, "max_ms": self.max_ms,
                "total_ms": self.total_ms}


class Ctx:
    """One run of one cell: its files, its seed, and the window.

    Drivers call ``setup_done()`` when the first timed operation is
    next, then run their loop inside ``window()``, which starts and
    stops the profiler when the run is traced.  ``span(name)`` marks a
    host span on the profiler's clock (a no-op when not traced).
    """

    TRACE_SECONDS = 4.0      # a traced run measures a short window

    def __init__(self, wl: dict, seed: int, seconds: float, trace: bool,
                 t_start: float, clock=None, control: bool = False):
        self.cell, self.config = wl["cell"], wl["config"]
        self.traffic = wl["traffic"]
        self.seed, self.trace, self.control = seed, trace, control
        self.seconds = min(seconds, self.TRACE_SECONDS) if trace else seconds
        self.t_start, self.clock = t_start, clock
        self.setup_s = None
        self.window_s = None
        self.compiles_in_window = None
        self.gc_in_window = None
        self.trace_dir = None
        self.t0 = None
        self.marks = []

    def mark(self, name: str) -> None:
        """Note how far set-up has come, in seconds since the start."""
        self.marks.append([name, time.perf_counter() - self.t_start])

    def elapsed(self) -> float:
        """Seconds since the window opened."""
        return time.perf_counter() - self.t0

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        import jax
        if self.setup_s is None:
            self.setup_done()
        before = self.clock.compiles if self.clock else 0
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        watch = GCWatch()
        gc.callbacks.append(watch)
        self.t0 = time.perf_counter()
        try:
            with self.span("bench.window"):
                yield
        finally:
            self.window_s = time.perf_counter() - self.t0
            gc.callbacks.remove(watch)
            self.gc_in_window = watch.summary()
            if self.trace:
                jax.profiler.stop_trace()
            if self.clock:
                self.compiles_in_window = self.clock.compiles - before

    def cleanup(self) -> None:
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def device_info() -> dict:
    """The device as JAX reports it, with the peak bytes of the fullest
    chip so far."""
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
