"""Snapshot crash loop: cycles of a committed snapshot, forced full
batches, a crash, and a restart from that snapshot and the stamp delta.

The registry is built as ``bench.sut.build_registry`` builds it and
wrapped in ``repro.store.snapshot.Snapshotter`` over a temporary
directory, as ``serve.py --snapshot-every`` wraps it.  Each cycle:

  snapshot  ``Snapshotter.snapshot()``: the capture, then the build and
            save on the snapshotter's thread while the batches run;
  batches   the configuration's ``snapshot.delta_batches`` forced full
            batches, whose updates are the stamp delta;
  wait      until the build has committed, then evict the stored
            snapshot from the page cache (outside the timing);
  crash     ``Snapshotter.recover(u)`` with a per-shard adversary ``u``
            drawn from the seed before the clock starts, then the first
            post-recovery batch.

``recover_ms`` is the time from the ``recover`` call (the snapshot's
read from the disk included) to that batch's results on the host, summed over
the crashes in the window and divided by their number, as in
``crash_loop``.  Before the loop the program compiles the restart for
every delta width a cycle can realise (``precompile_hybrid``), so no
restart compiles in the window; a program without it cannot run the cell
and the driver stops at once.

Besides the checks every registry cell has, ``fallback_recoveries``
counts the crashes that were restored by the full scan and not through
the snapshot (the program's ``registry.recover_fallbacks``); the control
(no snapshots) restores every crash by the full scan.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from bench import sut
from bench.traffic_gen import OpStream, draw_ring, rng_for


class SnapRig(sut.Rig):
    """The registry with a metrics registry attached, so that the
    snapshotter's counters can be read back."""

    full_scans = 0          # crashes the driver restored by the full scan

    def _build(self, ctx):
        from repro.obs import MetricsRegistry
        self.metrics = MetricsRegistry()
        return sut.build_registry(ctx.config, self.metrics)

    def counts(self) -> dict:
        out = super().counts()
        out["fallback_recoveries"] = self.full_scans + self.metrics.counter(
            "registry.recover_fallbacks").value
        return out


def delta_bounds(config: dict, traffic: dict) -> tuple:
    """Bounds on the fullest shard's delta slots in a cycle, from the
    shard's even share of the cycle's update lanes.  Upper: each update
    lane stamps at most one slot, so twice that share, and never more than
    the shard's pool.  Lower: at half fill about half the update lanes
    succeed and inserts refill the slots removes free, so a shard's delta
    is near a quarter of the share; its width covers down to half that."""
    mix = traffic["mix"]
    share = (mix["insert"] + mix["remove"]) / sum(mix.values())
    lanes = config["snapshot"]["delta_batches"] * config["batch"] * share
    even = lanes / config["shards"]
    per_shard = config["capacity"] // config["shards"]
    return int(even / 4), min(per_shard, int(2 * even))


def drop_cached(directory: str) -> None:
    """Evict the stored snapshots' files from the page cache.  They are
    fsynced at commit, so this loses nothing; a restart then reads them
    from the disk, as one after a power failure does.  A filesystem that
    ignores the advice (a 9p mount, for one) keeps them cached, and the
    restart's read stays a warm one."""
    for root, _, files in os.walk(directory):
        for name in files:
            fd = os.open(os.path.join(root, name), os.O_RDONLY)
            try:
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            finally:
                os.close(fd)


def run(ctx) -> dict:
    from repro.core import ShardedDurableMap
    if not hasattr(ShardedDurableMap, "precompile_hybrid"):
        raise SystemExit("snapshot_crash_loop: the program cannot compile "
                         "its restart ahead (no precompile_hybrid), so "
                         "restarts would compile in the window")
    from repro.store.snapshot import Snapshotter
    tr, cfg = ctx.traffic, ctx.config
    rig = SnapRig(ctx)
    b, per = rig.batch, cfg["snapshot"]["delta_batches"]
    stream = OpStream(tr, rig.universe, ctx.seed)
    ring = draw_ring(stream, tr["ring_batches"], b)
    adversary = rng_for(ctx.seed, 5)
    registry = rig.registry
    apply = registry.apply
    u_shape = (cfg["shards"], cfg["capacity"] // cfg["shards"])
    delta_gauge = rig.metrics.gauge("registry.last_recovery_from_delta_slots")
    directory = tempfile.mkdtemp(prefix="bench_snap_")
    snap = None if ctx.control else Snapshotter(registry, directory)
    n = 0

    def cycle() -> tuple:
        """One cycle; returns (seconds from the crash to the first
        post-recovery batch's results, the restart's delta slots)."""
        nonlocal n
        with ctx.span("bench.snapshot"):
            if snap is not None:
                snap.snapshot()
        with ctx.span("bench.apply"):
            for _ in range(per):
                apply(*ring[n % len(ring)])
                n += 1
            rig.rec.note("psyncs", registry.psyncs)
        with ctx.span("bench.wait"):
            if snap is not None:
                snap.wait()
                drop_cached(directory)
        with ctx.span("bench.adversary"):
            u = adversary.random(u_shape).astype(np.float32)
        t = time.perf_counter()
        with ctx.span("bench.recover"):
            if snap is not None:
                snap.recover(u)
            else:
                registry.crash_and_recover(u)
                rig.full_scans += 1
            rig.rec.note("crash")
            apply(*ring[n % len(ring)])
        took = time.perf_counter() - t
        n += 1
        with ctx.span("bench.apply"):
            rig.rec.note("size", len(registry))
        return took, int(delta_gauge.value)

    try:
        widths = [] if snap is None else registry.precompile_hybrid(
            *delta_bounds(cfg, tr))
        ctx.mark("restart_compiled")
        for _ in range(tr["warm_cycles"]):
            cycle()
        ctx.mark("warmed")
        took_ms, delta = [], 0
        with ctx.window():
            while ctx.elapsed() < ctx.seconds:
                took, slots = cycle()
                took_ms.append(took * 1e3)
                delta += slots
        crashes = len(took_ms)
        snapshots = 0 if snap is None else snap.snapshots
    finally:
        if snap is not None:
            snap.close()
        shutil.rmtree(directory, ignore_errors=True)
    return {"rig": rig,
            "end_to_end": {"recover_ms": sum(took_ms) / max(crashes, 1)},
            "attempted": crashes,
            "counts": {"crashes": crashes, "delta_slots": delta},
            "info": {"crashes": crashes, "batches": n,
                     "snapshots": snapshots,
                     "delta_slots_per_crash": delta / max(crashes, 1),
                     "restart_widths": widths,
                     "recover_ms_p50_max": [
                         float(np.median(took_ms)) if took_ms else None,
                         max(took_ms, default=None)],
                     "recoveries_hybrid": rig.metrics.counter(
                         "registry.recoveries_hybrid").value}}
