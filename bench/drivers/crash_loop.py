"""Crash loop: cycles of mixed full batches, all forced, then a crash and
a full recovery.

Each crash draws an independent per-shard adversary ``u`` from the seed
before the clock starts.  ``recover_ms`` is the time from the
``crash_and_recover`` call to the first post-recovery batch's results on
the host, summed over the crashes in the window and divided by their
number.  Between cycles the run records the registry's psyncs (SOFT: one
per successful update since the last recovery, none for the recovery
itself) and its live count, for the reference to check.
"""
from __future__ import annotations

import time

import numpy as np

from bench.sut import Rig
from bench.traffic_gen import OpStream, draw_ring, rng_for


def run(ctx) -> dict:
    tr = ctx.traffic
    rig = Rig(ctx)
    b, per = rig.batch, tr["batches_per_cycle"]
    stream = OpStream(tr, rig.universe, ctx.seed)
    ring = draw_ring(stream, tr["ring_batches"], b)
    adversary = rng_for(ctx.seed, 5)
    apply = rig.registry.apply
    cfg = ctx.config
    u_shape = (cfg["shards"], cfg["capacity"] // cfg["shards"])
    n = 0

    def cycle_and_crash() -> float:
        """The rest of a cycle, the crash, recovery and the next batch;
        returns the seconds from the crash to that batch's results."""
        nonlocal n
        with ctx.span("bench.apply"):
            for _ in range(per - 1):
                apply(*ring[n % len(ring)])
                n += 1
            rig.rec.note("psyncs", rig.registry.psyncs)
        with ctx.span("bench.adversary"):
            u = adversary.random(u_shape).astype(np.float32)
        t = time.perf_counter()
        with ctx.span("bench.recover"):
            rig.registry.crash_and_recover(u)
            rig.rec.note("crash")
            apply(*ring[n % len(ring)])
        took = time.perf_counter() - t
        n += 1
        with ctx.span("bench.apply"):
            rig.rec.note("size", len(rig.registry))
        return took

    # warm: two crashes, since a recovered state's planes are typed
    # differently from a fresh one's and the crash compiles again for them
    apply(*ring[0])
    n = 1
    cycle_and_crash()
    cycle_and_crash()
    ctx.mark("warmed")
    crashes, total = 0, 0.0
    with ctx.window():
        while ctx.elapsed() < ctx.seconds:
            total += cycle_and_crash()
            crashes += 1
    capacity = cfg["capacity"]
    return {"rig": rig,
            "end_to_end": {"recover_ms": total / max(crashes, 1) * 1e3},
            "attempted": crashes,
            "counts": {"crashes": crashes,
                       "scanned_slots": crashes * capacity},
            "info": {"crashes": crashes, "batches": n}}
